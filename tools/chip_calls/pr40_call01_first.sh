#!/bin/bash
# PR 40, chip call 1 (1 chip): the claimed cell serve-lfm2-agent-closed128: the working tree against build/parent =
# `git archive b8b83c2`, tracing off, order parent, change, change, parent on two seeds, then one traced run of the
# change (mixed_ahead_pct, the starved table, the launches table).
out=/root/repo/chiprun_out/p40c1; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window\|starved\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-1800
}
L=serve-lfm2-agent-closed128
run $L parent 4000000011 0; run $L change 4000000011 0; run $L change 4000000012 0; run $L parent 4000000012 0
run $L change 4000000013 1
