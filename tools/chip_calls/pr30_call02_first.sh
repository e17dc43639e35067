#!/bin/bash
# PR 30, chip call 2 (1 chip; call 1 was the prototype's microbenchmark, build/pr30/bench.py,
# not kept: its table is PERF.md section 6's "call 1"): chip_smoke.py on the working tree
# (the self-test now holds the decode walk against the dense read at the three cells' pools),
# then the first look at the cells: the chat cell traced on the change, two pairs of it
# against build/parent = `git archive d83890a` (order parent, change, change, parent, a seed
# per pair), one pair each of the OLMoE and the Qwen3-Next cell.
out=/root/repo/chiprun_out/p30c2; mkdir -p $out
( python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 600 $out/chip_smoke.log)"
cp /root/repo/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|attention route\|device ms by scope" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
run serve-mistral7b-chat-steady change 3000000011 1
run serve-mistral7b-chat-steady parent 3000000021 0; run serve-mistral7b-chat-steady change 3000000021 0
run serve-mistral7b-chat-steady change 3000000022 0; run serve-mistral7b-chat-steady parent 3000000022 0
run serve-olmoe-chat-closed32 parent 3000000031 0; run serve-olmoe-chat-closed32 change 3000000031 0
run serve-qwen3next-longchat-closed32 parent 3000000041 0; run serve-qwen3next-longchat-closed32 change 3000000041 0
