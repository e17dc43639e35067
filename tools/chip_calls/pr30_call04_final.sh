#!/bin/bash
# PR 30, chip call 4 (1 chip): build/archive_check = `git archive $(git write-tree)`, the tree as
# committed but for this call's numbers, beside build/parent = `git archive d83890a`: chip_smoke.py
# (41 self-test cases, the decode walk against the dense read among them), one more pair of the
# claimed cell, the chat cell traced, three more OLMoE runs of the change on seeds of their own
# (its `correct` has the smallest margin) with one parent beside them, and one Qwen3-Next run.
out=/root/repo/chiprun_out/p30c4; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 400 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|attention route\|device ms per decode_tick\|logits vs" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
run serve-mistral7b-chat-steady parent 3000000101 0; run serve-mistral7b-chat-steady archive_check 3000000101 0
run serve-mistral7b-chat-steady archive_check 3000000102 1
run serve-olmoe-chat-closed32 archive_check 3000000111 0; run serve-olmoe-chat-closed32 parent 3000000111 0
run serve-olmoe-chat-closed32 archive_check 3000000112 0
run serve-olmoe-chat-closed32 archive_check 3000000113 0
run serve-qwen3next-longchat-closed32 archive_check 3000000121 0
