#!/bin/bash
# PR 45, chip calls 2..: serving cells, the parent (build/parent = `git archive 045f6ac`) beside the change, tracing off,
# in the order parent, change, change, parent, parent, change on PAIRS x 2 seeds a cell (default 3 seeds: PAIRS=3 runs
# the first three of that order's seeds); then (TRACED cells) one traced run of the change through
# tools/chip_calls/pr45_traced_cell.py (the contract line, and the latent key-step counters summed over the window) and
# one of the parent on the same seed.
#   SEEDS=3 bash tools/chip_calls/pr45_call02_cells.sh p45c2 4500000020 serve-moonlight-longdoc-closed64
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
change=${CHANGE:-/root/repo}
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    if [ $2 = change ] && [ $4 = 1 ]; then
        ( cd $dir && python3 tools/chip_calls/pr45_traced_cell.py $1 $3 \
            > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    else
        ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
            > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    fi
    echo "$1 $2 seed $3 trace $4: rc $? $(grep -v '^#' $out/$1.$2.s$3.t$4.log | tail -1 | cut -c1-${5:-900})"
    grep '^# latent' $out/$1.$2.s$3.t$4.log
}
for cell in "$@"; do
    order=(parent change change parent parent change)
    for i in $(seq 0 $((${SEEDS:-3} - 1))); do
        n=$((n + 1)); first=${order[$((i % 3 * 2))]}; second=${order[$((i % 3 * 2 + 1))]}
        run $cell $first $n 0; run $cell $second $n 0
    done
    case " ${TRACED-serve-moonlight-longdoc-closed64} " in
        *" $cell "*) n=$((n + 1)); run $cell change $n 1 9000; run $cell parent $n 1 9000;;
    esac
done
exit 0
