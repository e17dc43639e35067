#!/bin/bash
# PR 41, chip call 3 (1 chip): the claimed cell serve-trinity-mixedlen-closed32: the working tree against build/parent =
# `git archive 428ceb4`, tracing off, order parent, change, change, parent on two seeds, then two more seeds of the change,
# then one traced run of each side (device_ops, swa_read / full_read / mixed_exec, hbm_peak_gb).
out=/root/repo/chiprun_out/p41c3; mkdir -p $out
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-6000)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-1200
}
T=serve-trinity-mixedlen-closed32
run $T parent 4100000021 0; run $T change 4100000021 0; run $T change 4100000022 0; run $T parent 4100000022 0
run $T change 4100000023 0; run $T change 4100000024 0
run $T change 4100000025 1; run $T parent 4100000025 1
