#!/bin/bash
# PR 52, call 3 (1 chip), at the committed seeding: the fault table on one
# seed, the check's clean reading over six more, then the cell as the driver
# runs it: six runs tracing off on seeds of their own (two over 2**31) through
# tools/measure.py (medians and spreads), and one traced run.
#   bash benchmark/tools/calls/pr52_call03_cell.sh <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
n=$1
python3 benchmark/tools/calls/pr52_faults.py $((n+7)) 2>&1 | filter | tee $out/call03_faults.log
python3 benchmark/tools/calls/pr52_faults.py ONLY=clean $((n+1)) $((n+2)) $((n+3)) $((n+4)) $((n+5)) $((n+6)) 2>&1 | filter | tee $out/call03_clean.log
python3 benchmark/tools/measure.py --tag pr52_call03 --sets 1 --runs 4 --seed0 $((n+20)) serve-longcat-avturn-closed64 2>&1 | tail -40 | cut -c1-1500
python3 benchmark/tools/measure.py --tag pr52_call03_big --sets 1 --runs 2 --seed0 3152000052 serve-longcat-avturn-closed64 2>&1 | tail -25 | cut -c1-1500
python3 benchmark/run.py --workload serve-longcat-avturn-closed64 --seed $((n+40)) \
    --seconds 51 --trace 1 > $out/call03_cell_t1.log 2> $out/call03_cell_t1.err
echo "cell trace 1: rc $? $(tail -1 $out/call03_cell_t1.log | cut -c1-7000)"
grep -h "^# serve: \(window\|token gap\|weights\|prefill+decode\|shape ladder\|pre-roll\)\|^# .*set-up\|roofline\|scope\b" $out/call03_cell_t1.log | cut -c1-900
