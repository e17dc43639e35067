#!/bin/bash
# PR 41, chip call 4 (1 chip): the other cells whose programs the change touches, each tracing off parent then change
# on one seed, then one traced run of the change (device_ops, the read's scope metrics): OLMoE, long-prompt, Qwen3-Next,
# the chat cell.  Parent = build/parent = `git archive 428ceb4`.  Two calls of two cells each (a call lasts an hour at most):
#   bash tools/chip_calls/pr41_call04_others.sh p41c4 4100000040 serve-olmoe-chat-closed32 serve-mistral7b-longprompt-closed
#   bash tools/chip_calls/pr41_call04_others.sh p41c5 4100000050 serve-qwen3next-longchat-closed32 serve-mistral7b-chat-steady
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-6000)"
    grep -h "token gap p50\|logits vs\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-800
}
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
    n=$((n + 1)); run $cell change $n 1
done
