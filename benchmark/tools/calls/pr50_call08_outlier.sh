#!/bin/bash
# PR 50, call 8 (1 chip): the one clean reading of the check over 0.02 in 35
# (0.0248, seed 3100000707, call 7): is it the top-k's swaps (it then falls
# with o_proj's scale) or a routing flip (it stays)?  The check alone on that
# seed at ATTN_OUT 0.3 (committed), 0.1 and 1.
#   bash benchmark/tools/calls/pr50_call08_outlier.sh <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr50; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
for s in 0.3 0.1 1.0; do
    python3 benchmark/tools/calls/pr50_faults.py ATTN_OUT=$s ONLY=clean $1 2>&1 | filter | tee $out/call08_clean_$s.log
done
true
