#!/bin/bash
# PR 50, call 2 (1 chip): the check's clean reading over three seeds and
# the fault table on one, at several scales of o_proj (ATTN_OUT of
# benchmark/families/glm_moe_dsa.py): how the value in that file was chosen.
#   SCALES="0.5 0.3" bash benchmark/tools/calls/pr50_call02_scales.sh <seed> <seed> <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr50; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
for s in ${SCALES:-0.5 0.3}; do
    python3 benchmark/tools/calls/pr50_faults.py ${KNOB:-ATTN_OUT}=$s ONLY=clean "$@" 2>&1 | filter | tee $out/call02_clean_$s.log
    python3 benchmark/tools/calls/pr50_faults.py ${KNOB:-ATTN_OUT}=$s ${ONLY:+ONLY=$ONLY} $1 2>&1 | filter | tee $out/call02_faults_$s.log
done
