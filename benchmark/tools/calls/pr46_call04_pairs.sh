#!/bin/bash
# PR 46, call 4 (1 chip): the new cell's own mix at 128 and 384 callers (one
# window each), then one cell of the two configurations that share
# `_causal_conv` and the slot pool and of one that shares the attention
# block, the parent (build/parent = `git archive 0831e92`, with this PR's
# BENCHMARK.json and benchmark/ laid over it as the driver does) beside the
# change, tracing off, one pair each on one seed.  No gain is claimed: the
# question is whether any end-to-end metric left its bound.
#   bash benchmark/tools/calls/pr46_call04_pairs.sh p46c4 4600000400 <cell> [<cell> ...]
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
for callers in ${CALLERS:-128 384}; do
    python3 benchmark/tools/calls/pr46_callers.py $callers $((n + callers)) \
        > $out/callers$callers.log 2> $out/callers$callers.err
    echo "callers $callers: rc $? $(tail -1 $out/callers$callers.log | cut -c1-900)"
    grep -h "window\|token gap" $out/callers$callers.log | cut -c1-600
done
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-${5:-900})"
}
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
done
