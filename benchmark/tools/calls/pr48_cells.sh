#!/bin/bash
# PR 48, the chip calls: serving cells, one process a run, every run on a seed of its own (but the two sides of a
# pair).  The parent is build/parent = `git archive d8f3377` with this PR's benchmark/ and BENCHMARK.json laid over
# it, as the driver lays them: its traced runs read no request phase (the reader returns None) and must not fail.
#   pr48_cells.sh <tag> <first seed> traced <cell>...   one --trace 1 run of the change a cell
#   pr48_cells.sh <tag> <first seed> cost <cell>...     PAIRS (default 2) times: parent untraced, parent traced,
#                                                       change traced, change untraced (the order turned every time)
# Every run's output goes to chiprun_out/<tag>/<cell>.<side>.s<seed>.t<0|1>.log; of a traced run the reader's
# "first token" lines are echoed, of every run the window's own line (tokens, ticks) and the tick / token gap medians.
out=/root/repo/chiprun_out/$1; n=$2; mode=$3; shift 3; mkdir -p $out
change=${CHANGE:-/root/repo}
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    local log=$out/$1.$2.s$3.t$4.log
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 > $log 2> ${log%.log}.err )
    echo "== $1 $2 seed $3 trace $4: rc $? $(grep -v '^#' $log | tail -1 | cut -c1-${5:-600})"
    grep -h '^# serve: window\|^# serve: token gap' $log | cut -c1-420
    [ $4 = 1 ] && grep -h '^# first token' $log | cut -c1-900
}
for cell in "$@"; do
    if [ $mode = traced ]; then
        n=$((n + 1)); run $cell change $n 1 5000
    else
        for i in $(seq 1 ${PAIRS:-2}); do
            n=$((n + 1))
            if [ $((i % 2)) = 1 ]; then
                run $cell parent $n 0; run $cell parent $n 1 200; run $cell change $n 1 200; run $cell change $n 0
            else
                run $cell change $n 0; run $cell change $n 1 200; run $cell parent $n 1 200; run $cell parent $n 0
            fi
        done
    fi
done
exit 0
