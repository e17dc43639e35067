#!/bin/bash
# PR 23, chip call 6 (1 chip), after the review: the tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`) against the parent
# (build/parent, see call 2).  What changed since call 4: `tick` is opened through
# Tracer.span like every other span, the counters nobody read are gone (the tick keeps
# kind and emitted, engine/build_batch tokens and bucket, read there by
# tick_attr_ratio), and both engines put names and locations into the persistent
# compile cache's key.  So: (1) chat cold, then the same seed traced on the warm cache
# (is set-up warm again, i.e. is the new key the same from run to run; token gap traced
# against untraced); (2) parent against change untraced on a second seed; (3) the
# long-prompt and the GPT-2 cell traced (every metric still reported), GPT-2 once more
# untraced (its warm set-up).
out=/root/repo/chiprun_out/p23c6; mkdir -p $out
run() {  # side cell seed trace
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-2200)"
}
c=serve-mistral7b-chat-steady; l=serve-mistral7b-longprompt-closed; g=train-gpt2large-d64-s1k
run change $c 2000000071 0; run change $c 2000000071 1
run parent $c 2000000071 0
run parent $c 2000000072 0; run change $c 2000000072 0
run change $l 2000000081 1
run change $g 2000000091 1; run change $g 2000000091 0
grep -h "token gap\|host ms per tick\|by scope\|kernels matching\|no such scope" $out/change.*.t1.log | cut -c1-1200
grep -h "token gap" $out/*chat*.t0.log | cut -c1-200
grep -h "set-up" $out/*.log | cut -c1-200
