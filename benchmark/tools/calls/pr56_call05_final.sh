#!/bin/bash
# PR 56, call 5: the committed files alone (build/archive_check = `git
# archive $(git write-tree)`): chip_smoke's gdn phase (RaggedQwen3Next
# through the shared mixer and the 4-D row blocks against the reference),
# the new cell traced with the three proposed metrics and untraced, the
# Qwen3-Next cell untraced, and the two lines of the faults table call 4
# lost to Mosaic's refusal of Precision.HIGH.
cd "$(dirname "$0")/../../../"
root=$PWD; out=$root/chiprun_out/pr56; mkdir -p $out
cd build/archive_check || exit 2
timeout -s KILL 900 python3 -c "
import json, chip_smoke
out = chip_smoke.run(phases=('gdn',))
print(json.dumps({k: out[k] for k in out if k in ('ok', 'gdn', 'device')})[:3000])
" 2>&1 | tail -5 | cut -c1-3000
C=serve-olmohybrid-evalgen-closed128
python3 benchmark/tools/calls/pr56_with_metrics.py --workload $C --seed 5600000031 --seconds 51 --trace 1 > $out/call05_cell_t1.txt 2>&1
echo "new cell traced: $(tail -1 $out/call05_cell_t1.txt | cut -c1-3500)"
python3 benchmark/run.py --workload $C --seed 2147483999 --seconds 51 --trace 0 > $out/call05_cell_t0.txt 2>&1
echo "new cell: $(tail -1 $out/call05_cell_t0.txt | cut -c1-600)"
python3 benchmark/run.py --workload serve-qwen3next-longchat-closed32 --seed 5600000033 --seconds 51 --trace 0 > $out/call05_q_t0.txt 2>&1
echo "qwen3next: $(tail -1 $out/call05_q_t0.txt | cut -c1-600)"
timeout -s KILL 900 python3 benchmark/tools/calls/pr56_faults.py ONLY=products_bf16,reference_low_precision 5600000056 2>&1 | grep "^seed" | tee $out/call05_faults.txt
