#!/bin/bash
# PR 56, call 2: the new cell's first runs on the chip: one untraced, one
# traced with the three proposed per-layer metrics in the line.
cd "$(dirname "$0")/../../.."
mkdir -p chiprun_out/pr56
python3 benchmark/run.py --workload serve-olmohybrid-evalgen-closed128 --seed 5600000001 --seconds 51 --trace 0 2>&1 | tee chiprun_out/pr56/call02_run0.txt | tail -40
python3 benchmark/tools/calls/pr56_with_metrics.py --workload serve-olmohybrid-evalgen-closed128 --seed 5600000002 --seconds 51 --trace 1 2>&1 | tee chiprun_out/pr56/call02_run1.txt | tail -120
