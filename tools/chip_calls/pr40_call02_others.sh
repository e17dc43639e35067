#!/bin/bash
# PR 40, chip call 2 (1 chip): the two other cells the issue expects to move, each the working tree against
# build/parent = `git archive b8b83c2`: serve-moonlight-longdoc-closed64 and serve-trinity-mixedlen-closed32, tracing
# off, order parent, change, change, parent on two seeds, then one traced run of the change.
out=/root/repo/chiprun_out/p40c2; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3300)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window\|starved\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-1800
}
for L in serve-moonlight-longdoc-closed64 serve-trinity-mixedlen-closed32; do
    run $L parent 4000000021 0; run $L change 4000000021 0; run $L change 4000000022 0; run $L parent 4000000022 0
    run $L change 4000000023 1
done
