#!/bin/bash
# PR 40, chip calls 3a / 3b (1 chip): the cells the change should leave level, each the working tree against
# build/parent = `git archive b8b83c2`: one untraced pair (parent, change) and one traced run of the change (does every
# per-layer metric still read a number?).  Cells are the arguments.
out=/root/repo/chiprun_out/p40c3; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3300)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window\|starved\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
for L in "$@"; do
    run $L parent 4000000031 0; run $L change 4000000031 0; run $L change 4000000033 1
done
