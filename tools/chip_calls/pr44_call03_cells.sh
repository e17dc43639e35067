#!/bin/bash
# PR 44, chip calls 3..: serving cells, the parent (build/parent = `git archive 04c5a97`) beside the change, tracing
# off, in the order parent, change, change, parent on two seeds a cell; then (TRACED cells) one traced run of the
# change through tools/chip_calls/pr44_traced_cell.py (the contract line, and the chunk-key-step counters summed over
# the window) and one of the parent on the same seed.
#   bash tools/chip_calls/pr44_call03_cells.sh p44c3 4400000030 serve-trinity-mixedlen-closed32
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
change=${CHANGE:-/root/repo}
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    if [ $2 = change ] && [ $4 = 1 ]; then
        ( cd $dir && python3 tools/chip_calls/pr44_traced_cell.py $1 $3 \
            > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    else
        ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
            > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    fi
    echo "$1 $2 seed $3 trace $4: rc $? $(grep -v '^#' $out/$1.$2.s$3.t$4.log | tail -1 | cut -c1-${5:-900})"
    grep '^# chunk' $out/$1.$2.s$3.t$4.log
}
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
    n=$((n + 1)); run $cell change $n 0; run $cell parent $n 0
    case " ${TRACED:-serve-trinity-mixedlen-closed32} " in
        *" $cell "*) n=$((n + 1)); run $cell change $n 1 7000; run $cell parent $n 1 7000;;
    esac
done
if [ -n "$BENCH" ]; then
    timeout -s KILL 600 python tools/chip_calls/pr44_kernel_bench.py --out $out/kernel.json cells $BENCH > $out/kernel.log 2> $out/kernel.err
    echo "kernel rc $?"; cat $out/kernel.log
fi
