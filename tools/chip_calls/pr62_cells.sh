#!/bin/bash
# PR 62, the chip calls that run cells: the parent (build/parent = `git archive b465f97`) beside the change (the
# working tree, or CHANGE=<dir>), one process a run.  For each cell: SEEDS untraced pairs (default 2), a seed a pair,
# in the order parent, change | change, parent | ..., then TRACED (default 1) traced pairs on seeds of their own,
# the change first (TRACED_PARENT=0: the change alone); after a traced run of the four-chip cell its collectives by operation and scope
# (`pr62_tp_ring.py --trace-ops`).  Every run's last line (the contract line) goes to
# chiprun_out/<tag>/<cell>.<side>.s<seed>.t<0|1>.log with the commentary above it; its first characters are echoed.
#   chiprun --chips 4 --timeout 2400 -- env SEEDS=1 TRACED=1 bash tools/chip_calls/pr62_cells.sh p62c1 6200000010 train-mistral7b-z3tp-s4k
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
change=${CHANGE:-/root/repo}
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(grep -v '^#' $out/$1.$2.s$3.t$4.log | tail -1 | cut -c1-${5:-900})"
    grep -h "tp_overlap" $out/$1.$2.s$3.t$4.err $out/$1.$2.s$3.t$4.log | head -2 | cut -c1-200
    if [ $4 = 1 ] && [ $1 = train-mistral7b-z3tp-s4k ]; then
        python3 /root/repo/tools/chip_calls/pr62_tp_ring.py --trace-ops $dir 6 $out/$1.$2.s$3.ops.json 2>&1 | cut -c1-260
    fi
}
for cell in "$@"; do
    for i in $(seq 1 ${SEEDS:-2}); do
        n=$((n + 1))
        if [ $((i % 2)) = 1 ]; then run $cell parent $n 0; run $cell change $n 0
        else run $cell change $n 0; run $cell parent $n 0; fi
    done
    for i in $(seq 1 ${TRACED:-1}); do
        n=$((n + 1)); run $cell change $n 1 6000
        [ "${TRACED_PARENT:-1}" = 1 ] && run $cell parent $n 1 6000
    done
done
exit 0
