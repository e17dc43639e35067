#!/bin/bash
# PR 28, chip call 3 (1 chip), the tree git would commit (build/archive_check =
# `git archive $(git write-tree)`) against build/parent = `git archive 4e4bfed`: chip_smoke.py,
# then the claimed cell (serve-mistral7b-chat-steady) parent, change, change, parent with
# tracing off, one more pair of each closed-loop cell, and a traced run of every serving cell
# on the change.
out=/root/repo/chiprun_out/p28c3; mkdir -p $out
( cd /root/repo/build/archive_check && python3 chip_smoke.py > $out/smoke1.log 2> $out/smoke1.err )
echo "chip_smoke on one chip: rc $? $(tail -1 $out/smoke1.log | cut -c1-600)"
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|decode ticks in the window\|mixed+prefill ticks in the window\|gmm roofline\|do not divide" \
        $out/$1.$2.s$3.t$4.log | cut -c1-600
}
run serve-mistral7b-chat-steady parent 2800000051 0; run serve-mistral7b-chat-steady archive_check 2800000051 0
run serve-mistral7b-chat-steady archive_check 2800000052 0; run serve-mistral7b-chat-steady parent 2800000052 0
for cell in serve-mistral7b-longprompt-closed serve-olmoe-chat-closed32; do
    run $cell archive_check 2800000053 0; run $cell parent 2800000053 0
done
run serve-mistral7b-chat-steady archive_check 2800000061 1
run serve-olmoe-chat-closed32 archive_check 2800000062 1
run serve-mistral7b-longprompt-closed archive_check 2800000063 1
