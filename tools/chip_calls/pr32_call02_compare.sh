#!/bin/bash
# PR 32, chip call 2 (1 chip): the probe once more, then the working tree against build/parent = `git archive 33bfaa0`, tracing off,
# order parent, change, change, parent, a seed a pair: the claimed cell first
# (serve-qwen3next-longchat-closed32, five more pairs), then three pairs of the Moonlight cell, two of the
# OLMoE cell (every expert held: the prediction is no change) and one each of the two Mistral serving cells
# (no grouped GEMM in their programs).
out=/root/repo/chiprun_out/p32c2; mkdir -p $out
# first the probe of call 1 again, one pass a form: the self-test case now hands each layer's weights over as an
# argument of their own (call 1 timed a 0.65 ms copy of a 268 MB slice beside every call)
( python tools/chip_calls/pr32_probe.py parent guarded dynamic > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; cut -c1-1800 $out/probe.log
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50" $out/$1.$2.s$3.t$4.log | cut -c1-600
}
pair() {  # cell seed seed
    run $1 parent $2 0; run $1 change $2 0; run $1 change $3 0; run $1 parent $3 0
}
pair serve-qwen3next-longchat-closed32 3200000041 3200000042
pair serve-qwen3next-longchat-closed32 3200000043 3200000044
run serve-qwen3next-longchat-closed32 change 3200000045 0; run serve-qwen3next-longchat-closed32 parent 3200000045 0
pair serve-moonlight-longdoc-closed64 3200000051 3200000052
pair serve-olmoe-chat-closed32 3200000061 3200000062
run serve-mistral7b-chat-steady parent 3200000071 0; run serve-mistral7b-chat-steady change 3200000071 0
run serve-mistral7b-longprompt-closed change 3200000072 0; run serve-mistral7b-longprompt-closed parent 3200000072 0
