#!/bin/bash
# PR 52, call 4, second half (1 chip): the two router faults that call 3
# read under the limit at BIAS_STD 2e-3 (bias_dropped 0.0286,
# bias_in_weights 0.0224), again at a larger seeded bias, beside the clean
# reading: how BIAS_STD of benchmark/families/longcat_flash.py was chosen.
#   bash benchmark/tools/calls/pr52_call04_bias.sh <seed> <std> [<std> ...]
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
seed=$1; shift
for std in "$@"; do
    python3 benchmark/tools/calls/pr52_faults.py BIAS_STD=$std ONLY=clean,bias_dropped,bias_in_weights $seed 2>&1 | filter | tee $out/call04_bias_$std.log
done
