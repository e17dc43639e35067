#!/bin/bash
# PR 34, chip call 4 (1 chip): more than one pair for the cells call 2 gave one: build/archive_check =
# `git archive $(git write-tree)` beside build/parent = `git archive 7202f98` (traced: build/parent_overlay, the parent
# under this PR's benchmark files). The long-prompt cell, whose mixed_idle_ms_tick did not fall in call 2's pair: two
# traced runs a side and two untraced pairs; OLMoE and Qwen3-Next: one traced run a side and one untraced pair more.
out=/root/repo/chiprun_out/p34c4; mkdir -p $out
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window made\|starved" $out/$1.$2.s$3.t$4.log | cut -c1-1200
}
Q=serve-qwen3next-longchat-closed32; O=serve-olmoe-chat-closed32; L=serve-mistral7b-longprompt-closed
run $L parent_overlay 3400000071 1; run $L archive_check 3400000072 1; run $L archive_check 3400000073 1; run $L parent_overlay 3400000074 1
run $L parent 3400000075 0; run $L archive_check 3400000075 0; run $L archive_check 3400000076 0; run $L parent 3400000076 0
run $O archive_check 3400000081 1; run $O parent_overlay 3400000082 1
run $O archive_check 3400000083 0; run $O parent 3400000083 0
run $Q parent_overlay 3400000091 1; run $Q archive_check 3400000092 1
run $Q parent 3400000093 0; run $Q archive_check 3400000093 0
