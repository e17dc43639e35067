#!/bin/bash
# PR 32, chip call 3 (1 chip): build/archive_check = `git archive $(git write-tree)`, the tree as committed
# but for this call's numbers, beside build/parent = `git archive 33bfaa0`: chip_smoke.py (44 self-test
# cases, `gmm_share` among them), the traced runs of the three MoE cells on the change and of the two share
# cells on the parent (each followed by scope_mixed.py: device ms of every step program by scope), then
# untraced pairs on seeds of their own: one more of the claimed cell, one of Moonlight, and the GPT-2-Large
# training cell (no grouped GEMM in its program) once a side.
out=/root/repo/chiprun_out/p32c3; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 400 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|device ms per decode_tick\|logits vs\|mixed+prefill ticks" $out/$1.$2.s$3.t$4.log | cut -c1-1500
    if [ $4 = 1 ]; then
        ( cd /root/repo/build/$2 && python3 /root/repo/tools/chip_calls/scope_mixed.py $1 2>&1 | cut -c1-1800 | tee $out/$1.$2.s$3.scopes.log )
    fi
}
run serve-qwen3next-longchat-closed32 archive_check 3200000081 1
run serve-qwen3next-longchat-closed32 parent 3200000082 1
run serve-moonlight-longdoc-closed64 archive_check 3200000083 1
run serve-moonlight-longdoc-closed64 parent 3200000084 1
run serve-olmoe-chat-closed32 archive_check 3200000085 1
run serve-qwen3next-longchat-closed32 archive_check 3200000091 0; run serve-qwen3next-longchat-closed32 parent 3200000091 0
run serve-moonlight-longdoc-closed64 parent 3200000093 0; run serve-moonlight-longdoc-closed64 archive_check 3200000093 0
run train-gpt2large-d64-s1k parent 3200000095 0; run train-gpt2large-d64-s1k archive_check 3200000095 0
