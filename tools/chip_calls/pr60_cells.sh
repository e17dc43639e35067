#!/bin/bash
# PR 60, the chip calls that run cells: the parent (build/parent = `git archive 5be9b94`) beside the change (the
# working tree, or CHANGE=<dir>), one process a run: benchmark/tools/calls/pr51_cells.sh's order (SEEDS untraced pairs a
# cell, a seed a pair, the sides alternating, then TRACED traced pairs on seeds of their own, the change first), the
# traced runs through tools/chip_calls/pr60_with_metrics.py (`moe_combine_ms_tick`, which BENCHMARK.json has no room
# to list; its data file is laid over the parent as the driver lays a PR's benchmark files).  Logs under
# chiprun_out/<tag>/<cell>.<side>.s<seed>.t<0|1>.log, the contract line last; its first characters are echoed.
#   chiprun --timeout 3500 -- env SEEDS=3 TRACED=1 bash tools/chip_calls/pr60_cells.sh p60c2 6000000010 serve-qwen3next-longchat-closed32
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
change=${CHANGE:-/root/repo}
cp /root/repo/benchmark/layer_metrics/moe_combine_ms_tick.json /root/repo/build/parent/benchmark/layer_metrics/
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    local prog=benchmark/run.py; [ $4 = 0 ] || prog=/root/repo/tools/chip_calls/pr60_with_metrics.py
    ( cd $dir && CHECKOUT=$dir timeout -s KILL 1200 python3 $prog --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(grep -v '^#' $out/$1.$2.s$3.t$4.log | tail -1 | cut -c1-${5:-1200})"
}
for cell in "$@"; do
    for i in $(seq 1 ${SEEDS:-2}); do
        n=$((n + 1))
        if [ $((i % 2)) = 1 ]; then run $cell parent $n 0; run $cell change $n 0
        else run $cell change $n 0; run $cell parent $n 0; fi
    done
    for i in $(seq 1 ${TRACED:-1}); do
        n=$((n + 1)); run $cell change $n 1 9000; run $cell parent $n 1 9000
    done
done
exit 0
