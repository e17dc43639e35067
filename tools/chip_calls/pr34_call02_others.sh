#!/bin/bash
# PR 34, chip call 2 (1 chip): the cells that share the changed code without being claimed. The working tree against
# build/parent = `git archive 7202f98`, tracing off, one seed a pair (Qwen3-Next two), then a traced run a side of the three
# other closed loops (the parent under this PR's benchmark files, build/parent_overlay) and of the chat cell's change side.
out=/root/repo/chiprun_out/p34c2; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window made\|starved" $out/$1.$2.s$3.t$4.log | cut -c1-1200
}
Q=serve-qwen3next-longchat-closed32; O=serve-olmoe-chat-closed32; L=serve-mistral7b-longprompt-closed; C=serve-mistral7b-chat-steady
run $Q parent 3400000021 0; run $Q change 3400000021 0; run $Q change 3400000022 0; run $Q parent 3400000022 0
run $O change 3400000023 0; run $O parent 3400000023 0
run $L parent 3400000024 0; run $L change 3400000024 0
run $C change 3400000025 0; run $C parent 3400000025 0
run $Q change 3400000031 1; run $Q parent_overlay 3400000032 1
run $O change 3400000033 1; run $O parent_overlay 3400000034 1
run $L change 3400000035 1; run $L parent_overlay 3400000036 1
run $C change 3400000037 1
