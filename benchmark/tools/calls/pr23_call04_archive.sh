#!/bin/bash
# PR 23, chip call 4 (1 chip): the final tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`, git-ignored, copied to the
# chip) against the parent (build/parent, see call 2).  Chat cell: the traced and the
# untraced run of one seed (token gap, tracing on against off) and two more pairs with
# tracing off; long-prompt cell: three more pairs with tracing off (its pairs of call 2
# read -0.3% and -3.0%, inside the closed loop's own scatter: more readings).
out=/root/repo/chiprun_out/p23c4; mkdir -p $out
run() {  # side cell seed trace
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-1800)"
}
c=serve-mistral7b-chat-steady; l=serve-mistral7b-longprompt-closed
run change $c 2000000051 1; run change $c 2000000051 0; run parent $c 2000000051 0
run parent $c 2000000052 0; run change $c 2000000052 0
run parent $l 2000000061 0; run change $l 2000000061 0
run change $l 2000000062 0; run parent $l 2000000062 0
run parent $l 2000000063 0; run change $l 2000000063 0
grep -h "token gap\|host ms per tick\|by scope" $out/change.$c.*.log | cut -c1-900
grep -h "set-up" $out/*.log | cut -c1-200
