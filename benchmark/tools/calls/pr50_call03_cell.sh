#!/bin/bash
# PR 50, call 3 (1 chip): the steps alone again (the selection at two bits a
# pass over the narrowest width, whole-block gathers), the check's clean
# reading over six more seeds and the fault table at the committed seeding,
# then the cell's first windows: one run with tracing off, one traced.
#   bash benchmark/tools/calls/pr50_call03_cell.sh <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr50; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
n=$1
python3 benchmark/tools/calls/pr50_call01_steps.py 2>&1 | filter | tee $out/call03_steps.log
python3 benchmark/tools/calls/pr50_faults.py ONLY=clean $((n+1)) $((n+2)) $((n+3)) $((n+4)) $((n+5)) $((n+6)) 2>&1 | filter | tee $out/call03_clean.log
python3 benchmark/tools/calls/pr50_faults.py $((n+7)) 2>&1 | filter | tee $out/call03_faults.log
for trace in 0 1; do
    python3 benchmark/run.py --workload serve-glm5-longctx-closed16 --seed $((n+10+trace)) \
        --seconds 51 --trace $trace > $out/call03_cell_t$trace.log 2> $out/call03_cell_t$trace.err
    echo "cell trace $trace: rc $? $(tail -1 $out/call03_cell_t$trace.log | cut -c1-3000)"
    grep -h "^# serve: \(window\|token gap\|weights\|prefill+decode\|shape ladder\|pre-roll\)" $out/call03_cell_t$trace.log | cut -c1-700
done
