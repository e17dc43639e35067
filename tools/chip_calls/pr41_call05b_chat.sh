#!/bin/bash
# PR 41, chip call 5b (1 chip): call 5's traced chat run read chat_dense_read_ms_tick 0.703 for the ledger's 0.614 (+14%):
# at four or five live rows of 32 what a ROW costs (spreading the queries, picking the heads' lanes) is what the walk costs.
# After that work was cut to a lane block at a time and skipped on pad rows: the probe at the chat cell's occupancy (5% and
# 12% of the pool held), parent then change, then the chat cell traced on both sides and untraced parent, change, change, parent.
out=/root/repo/chiprun_out/p41c5b; mkdir -p $out
cd /root/repo
python3 tools/chip_calls/pr41_walk_probe.py build/parent $out/walk.parent.json shares=0.05,0.12,0.5 mistral7b olmoe trinity 2> $out/walk.parent.err
python3 tools/chip_calls/pr41_walk_probe.py . $out/walk.change.json shares=0.05,0.12,0.5 mistral7b olmoe trinity qwen3next 2> $out/walk.change.err
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50" $out/$1.$2.s$3.t$4.log | cut -c1-200
}
C=serve-mistral7b-chat-steady
run $C parent 4100000061 1; run $C change 4100000061 1
run $C parent 4100000062 0; run $C change 4100000062 0; run $C change 4100000063 0; run $C parent 4100000063 0
