#!/bin/bash
# PR 52, call 5 (1 chip): the committed files alone (build/archive_check =
# `git archive $(git write-tree)` of the final tree): the fault table on one
# seed at the committed seeding (BIAS_STD 3e-3), the new cell on six seeds
# tracing off in one call (the spread; two of the seeds over 2**31), then
# `--trace 1` on the new cell and on the accepted cell that shares its
# mixer.
#   bash benchmark/tools/calls/pr52_call05_final.sh <seed> x6
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; mkdir -p $out
new=serve-longcat-avturn-closed64; old=serve-moonlight-longdoc-closed64
a=$root/build/archive_check
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
run() {  # cell seed trace [chars]
    ( cd $a && python3 benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace $3 \
        > $out/c5.$1.s$2.t$3.log 2> $out/c5.$1.s$2.t$3.err )
    echo "$1 archive seed $2 trace $3: rc $? $(tail -1 $out/c5.$1.s$2.t$3.log | cut -c1-${4:-700})"
    grep -h "^# serve: \(window\|prefill+decode\)" $out/c5.$1.s$2.t$3.log | cut -c1-420
}
for seed in "$@"; do run $new $seed 0; done
run $new $(( $1 + 7 )) 1 9000
run $old $(( $1 + 8 )) 1 6000
( cd $a && python3 benchmark/tools/calls/pr52_faults.py $(( $1 + 9 )) 2>&1 | filter | tee $out/c5_faults.log )
