#!/bin/bash
# PR 23, chip call 8 (1 chip): the final tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`, made after the last edit to
# the program: since call 6 the `_span` helpers of scheduler and engine were replaced
# by `open_span` at the sites, nothing else).  The chat cell traced and untraced on one
# seed (token gap, tracing on against off, on the tree that is committed) and the
# long-prompt cell untraced.
out=/root/repo/chiprun_out/p23c8; mkdir -p $out
run() {  # cell seed trace
    ( cd /root/repo/build/archive_check && python3 benchmark/run.py --workload $1 --seed $2 \
        --seconds 51 --trace $3 > $out/change.$1.s$2.t$3.log 2> $out/change.$1.s$2.t$3.err )
    echo "change $1 seed $2 trace $3: rc $? $(tail -1 $out/change.$1.s$2.t$3.log | cut -c1-2200)"
}
c=serve-mistral7b-chat-steady; l=serve-mistral7b-longprompt-closed
run $c 2000000111 1; run $c 2000000111 0; run $l 2000000121 0
grep -h "token gap\|host ms per tick\|by scope\|no such scope\|set-up" $out/*.log | cut -c1-900
