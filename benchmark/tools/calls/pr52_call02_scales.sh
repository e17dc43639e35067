#!/bin/bash
# PR 52, call 2 (1 chip): the latent kernels at 64 heads against plain
# compositions, then the check's clean reading at seedings that give the
# scores and the values Moonlight's spread (q_b_proj / s_q, kv_b_proj /
# s_kv), two seeds each: how Q_SCALE and KV_B_SCALE of
# benchmark/families/longcat_flash.py were chosen.
#   bash benchmark/tools/calls/pr52_call02_scales.sh <seed> <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr52; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn\|InferenceEngineV2:"; }
python3 benchmark/tools/calls/pr52_call02_kernels.py 2>&1 | filter | tee $out/call02_kernels.log
for s in "Q_SCALE=1.5 KV_B_SCALE=0.2887" "Q_SCALE=0.433 KV_B_SCALE=1.0" "Q_SCALE=0.75 KV_B_SCALE=0.2887"; do
    python3 benchmark/tools/calls/pr52_faults.py $s ONLY=clean "$@" 2>&1 | filter | tee "$out/call02_clean_$(echo $s | tr ' =' '__').log"
done
