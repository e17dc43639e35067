#!/bin/bash
# PR 28, chip call 2 (1 chip): the working tree against build/parent = `git archive 4e4bfed`,
# tracing off, order parent, change, change, parent, a seed per pair: the claimed cell first
# (serve-mistral7b-chat-steady, four seeds = four pairs), then the two other serving cells (two seeds each).
# Then two traced runs: the long-prompt cell on the change again (call 1's run lost 3.6 s to
# one tick), and the chat cell on build/parent_overlay = the parent with this PR's
# BENCHMARK.json and benchmark/ laid over it, as the driver traces the parent (the two new
# metrics must be left out there, not raise).
out=/root/repo/chiprun_out/p28c2; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|decode ticks in the window\|mixed+prefill ticks in the window\|gmm roofline\|do not divide" \
        $out/$1.$2.s$3.t$4.log | cut -c1-600
}
for seed in 2800000021 2800000022; do
    run serve-mistral7b-chat-steady parent $seed 0; run serve-mistral7b-chat-steady change $seed 0
    seed=$((seed + 100))
    run serve-mistral7b-chat-steady change $seed 0; run serve-mistral7b-chat-steady parent $seed 0
done
for cell in serve-olmoe-chat-closed32 serve-mistral7b-longprompt-closed; do
    run $cell parent 2800000031 0; run $cell change 2800000031 0
    run $cell change 2800000032 0; run $cell parent 2800000032 0
done
run serve-mistral7b-longprompt-closed change 2800000041 1
run serve-mistral7b-chat-steady parent_overlay 2800000042 1
