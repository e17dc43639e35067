#!/bin/bash
# PR 33, chip call 2 (1 chip): what tracing costs when it is on, and the second traced seeds.
# The working tree beside build/parent = `git archive 0aeaccd` and build/parent_overlay = the
# parent with this PR's BENCHMARK.json and benchmark/ laid over it (as the driver traces the
# parent: the new readers must find nothing there and leave their nine metrics out).
# Chat cell: untraced parent, change, change, parent (itl_p50_ms), then one traced run a side
# (the token gap p50 a traced run logs).  Then a second traced seed of the three MoE cells.
out=/root/repo/chiprun_out/p33c2; mkdir -p $out
run() {  # cell tree seed trace
    local dir=/root/repo; [ $2 != change ] && dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3800)"
    grep -h "token gap p50\|launches\|made .* launches\|set-up" $out/$1.$2.s$3.t$4.log | cut -c1-1700
    tail -2 $out/$1.$2.s$3.t$4.err | grep -v "warnings.warn\|hugepages" | cut -c1-400
}
c=serve-mistral7b-chat-steady
run $c parent 3300000031 0; run $c change 3300000031 0
run $c change 3300000032 0; run $c parent 3300000032 0
run $c parent_overlay 3300000033 1; run $c change 3300000033 1
run serve-qwen3next-longchat-closed32 change 3300000041 1
run serve-olmoe-chat-closed32 change 3300000042 1
run serve-moonlight-longdoc-closed64 change 3300000043 1
