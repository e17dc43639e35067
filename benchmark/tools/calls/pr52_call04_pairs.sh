#!/bin/bash
# PR 52, call 4 (1 chip): one cell of each configuration that shares the
# touched files (ragged_deepseek_v3.py, modules/moe.py, grouped_gemm.py,
# engine_v2.py, scheduler.py): the parent (build/parent = `git archive
# 09e734c` with this PR's BENCHMARK.json and benchmark/ laid over it,
# pr52_overlay.sh) beside the change, tracing off, one pair each on one
# seed, parent / change / change / parent for the first cell; then the
# parent on an accepted cell with `--trace 1` (the benchmark as this PR
# leaves it has to run on a program that lacks what this PR adds) and on the
# new cell (it has to fail at once).  No gain is claimed: the question is
# whether any end-to-end metric left its bound.
#   bash benchmark/tools/calls/pr52_call04_pairs.sh <seed> <cell> [<cell> ...]
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; n=$1; shift; mkdir -p $out
new=serve-longcat-avturn-closed64
run() {  # cell side seed trace [chars]
    local dir=$root; [ $2 = change ] || dir=$root/build/$2
    ( cd $dir && timeout 900 python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/c4.$1.$2.s$3.t$4.log 2> $out/c4.$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/c4.$1.$2.s$3.t$4.log | cut -c1-${5:-900})"
}
t0=$(date +%s); run $new parent $n 0
echo "parent on the new cell: $(( $(date +%s) - t0 )) s: $(tail -1 $out/c4.$new.parent.s$n.t0.err | cut -c1-300)"
first=$1
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
done
n=$((n + 1)); run $first change $n 0; run $first parent $n 0
n=$((n + 1)); run $first parent $n 1 6000
