#!/bin/bash
# PR 52, call 1 (1 chip): does every program lower and run at H = 64, what
# does the check read with every layer right (three seeds), which faults
# does it see (one seed), then the cell's first windows: one run with
# tracing off, one traced.
#   bash benchmark/tools/calls/pr52_call01_first.sh <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
n=$1
python3 benchmark/tools/calls/pr52_faults.py ONLY=clean $((n+1)) $((n+2)) $((n+3)) 2>&1 | filter | tee $out/call01_clean.log
for trace in 0 1; do
    python3 benchmark/run.py --workload serve-longcat-avturn-closed64 --seed $((n+10+trace)) \
        --seconds 51 --trace $trace > $out/call01_cell_t$trace.log 2> $out/call01_cell_t$trace.err
    echo "cell trace $trace: rc $? $(tail -1 $out/call01_cell_t$trace.log | cut -c1-6000)"
    grep -h "^# serve: \(window\|token gap\|weights\|prefill+decode\|shape ladder\|pre-roll\)\|^# .*set-up" $out/call01_cell_t$trace.log | cut -c1-700
    tail -5 $out/call01_cell_t$trace.err | cut -c1-600
done
python3 benchmark/tools/calls/pr52_faults.py $((n+7)) 2>&1 | filter | tee $out/call01_faults.log
