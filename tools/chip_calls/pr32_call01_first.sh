#!/bin/bash
# PR 32, chip call 1 (1 chip): what a skipped work unit costs (pr32_probe.py: the parent's body, the
# guarded one and a dynamic bound on the work-unit axis, the three share shapes), then the claimed cell
# (serve-qwen3next-longchat-closed32) parent, change, change, parent with tracing off, a seed a pair, then
# the traced runs of the change in the two share cells. build/parent = `git archive 33bfaa0`.
out=/root/repo/chiprun_out/p32c1; mkdir -p $out
( python tools/chip_calls/pr32_probe.py > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; cut -c1-1800 $out/probe.log
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|device ms by scope" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
pair() {  # cell seed seed
    run $1 parent $2 0; run $1 change $2 0; run $1 change $3 0; run $1 parent $3 0
}
pair serve-qwen3next-longchat-closed32 3200000011 3200000012
run serve-qwen3next-longchat-closed32 change 3200000021 1
run serve-moonlight-longdoc-closed64 change 3200000022 1
run serve-moonlight-longdoc-closed64 parent 3200000031 0
run serve-moonlight-longdoc-closed64 change 3200000031 0
