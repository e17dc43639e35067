#!/bin/bash
# PR 23, chip call 2 (1 chip): the three one-chip cells, parent against change with
# tracing off (parent, change, change, parent; two seeds a cell), then the traced runs:
# the change on the seed of its first untraced run (token gap traced against untraced),
# and the parent under this PR's benchmark files (an old cell's traced run must not
# fail on a program that has none of the new spans, names or scopes).
# build/parent holds `git archive fb2f66d7` with BENCHMARK.json and benchmark/ of this
# PR laid over it (git-ignored, copied to the chip).
out=/root/repo/chiprun_out/p23c2; mkdir -p $out
run() {  # side cell seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-1500)"
}
i=0
for cell in serve-mistral7b-chat-steady serve-mistral7b-longprompt-closed train-gpt2large-d64-s1k; do
    a=$((2000000011 + i)); b=$((2000000012 + i)); i=$((i + 10))
    run parent $cell $a 0; run change $cell $a 0; run change $cell $b 0; run parent $cell $b 0
    run change $cell $a 1; run parent $cell $a 1
done
grep -h "token gap\|host ms per tick\|by scope\|kernels matching" $out/*.t1.log | cut -c1-1200
grep -h "token gap" $out/*chat*.t0.log | cut -c1-200
