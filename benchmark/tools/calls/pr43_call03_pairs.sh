#!/bin/bash
# PR 43, calls 3 and 4 (1 chip): one cell of every accepted serving configuration (all run the state
# manager, the cache and the engine this PR touched), the parent (build/parent = `git archive
# 9ea1495`, with this PR's BENCHMARK.json and benchmark/ laid over it as the driver does) beside the
# change, tracing off, in the order parent, change, change, parent on two seeds (PAIRS=1: parent,
# change on one).  No gain is claimed: the question is whether any end-to-end metric left its bound.
#   bash benchmark/tools/calls/pr43_call03_pairs.sh p43c3 4300000300 <cell> [<cell> ...]
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-${5:-900})"
}
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
    [ "${PAIRS:-2}" = 1 ] && continue
    n=$((n + 1)); run $cell change $n 0; run $cell parent $n 0
done
