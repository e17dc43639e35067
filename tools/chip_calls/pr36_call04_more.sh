#!/bin/bash
# PR 36, chip call 4 (1 chip), the trees of call 3: the OLMoE cell traced on both sides (does `gmm_roofline_pct` read,
# and under 100?), one more untraced Qwen3-Next pair (call 2's first lost 3.8 s of its window to one stalled tick on the
# change's side) and a second traced run of the change in the claimed cell on a large seed.
out=/root/repo/chiprun_out/p36c4; mkdir -p $out
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-700)"
    grep -h "token gap p50\|logits vs" $out/$1.$2.s$3.t$4.log | cut -c1-400
}
O=serve-olmoe-chat-closed32
run $O parent 3600000061 1; run $O archive_check 3600000061 1
Q=serve-qwen3next-longchat-closed32
run $Q archive_check 3600000071 0; run $Q parent 3600000071 0
run serve-lfm2-agent-closed128 archive_check 977312645 1
