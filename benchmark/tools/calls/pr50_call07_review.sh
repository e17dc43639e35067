#!/bin/bash
# PR 50, call 7 (1 chip), after the review: the mix back at ISSUE 50's
# `start_stagger_s` 12 and `_mla`'s dense read a method of its own.  The
# committed files alone (build/archive_check = `git archive $(git
# write-tree)`): the parent on the new cell (it has to fail at once), the
# new cell on six seeds none used before, tracing off, in one call (the
# spread), `--trace 1` on two more seeds (two phases of the loop), and the
# accepted cell that shares the model file, traced.
#   bash benchmark/tools/calls/pr50_call07_review.sh <seed> x6
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr50; mkdir -p $out
new=serve-glm5-longctx-closed16; old=serve-moonlight-longdoc-closed64
run() {  # dir cell seed trace [chars]
    ( cd $root/build/$1 && timeout 900 python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/c7.$2.$1.s$3.t$4.log 2> $out/c7.$2.$1.s$3.t$4.err )
    echo "$2 $1 seed $3 trace $4: rc $? $(tail -1 $out/c7.$2.$1.s$3.t$4.log | cut -c1-${5:-700})"
    grep -h "^# serve: \(window\|prefill+decode\)" $out/c7.$2.$1.s$3.t$4.log | cut -c1-420
}
t0=$(date +%s); run parent $new $(( $1 + 9 )) 0
echo "parent on the new cell: $(( $(date +%s) - t0 )) s: $(tail -1 $out/c7.$new.parent.s$(( $1 + 9 )).t0.err | cut -c1-300)"
for seed in "$@"; do run archive_check $new $seed 0; done
run archive_check $new $(( $1 + 7 )) 1 9000
run archive_check $new $(( $2 + 7 )) 1 9000
run archive_check $old $(( $1 + 8 )) 1 6000
