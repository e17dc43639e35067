#!/bin/bash
# PR 32, chip call 4 (1 chip): three more pairs of the claimed cell and one more of the OLMoE cell on seeds of
# their own, build/archive_check (the committed code) beside build/parent = `git archive 33bfaa0`, tracing off,
# the side that runs first alternating.
out=/root/repo/chiprun_out/p32c4; mkdir -p $out
run() {  # cell side seed
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace 0 \
        > $out/$1.$2.s$3.t0.log 2> $out/$1.$2.s$3.t0.err )
    echo "$1 $2 seed $3: rc $? $(tail -1 $out/$1.$2.s$3.t0.log | cut -c1-600)"
    grep -h "token gap p50\|logits vs" $out/$1.$2.s$3.t0.log | cut -c1-200
}
q=serve-qwen3next-longchat-closed32
run $q archive_check 2147483999; run $q parent 2147483999
run $q parent 1618033988; run $q archive_check 1618033988
run $q archive_check 977312645; run $q parent 977312645
run serve-olmoe-chat-closed32 parent 1203555967; run serve-olmoe-chat-closed32 archive_check 1203555967
