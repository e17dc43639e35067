#!/bin/bash
# PR 59, call 1: both Mamba-2 kernels at the cell's shapes against their XLA
# compositions, a few channel blocks each (tools/kernel_selftest.py ssd); the
# new cell once untraced and once traced with the five waiting metrics; the
# faults table of benchmark/tools/calls/pr59_faults.py at one seed (it stops
# at once if the clean program is over the limit).
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr59; mkdir -p $out
C=serve-granite4h-agent-closed128
timeout -s KILL 900 python3 tools/kernel_selftest.py ssd 1024,2048,4096 512,1024,2048 > $out/call01_kernels.txt 2>&1
echo "kernels: exit $?"; grep -v Warn $out/call01_kernels.txt | tail -60
timeout -s KILL 900 python3 benchmark/run.py --workload $C --seed 5900000001 --seconds 51 --trace 0 > $out/call01_cell_t0.txt 2>&1
echo "cell untraced: exit $?"; grep "logits vs\|resident\|shape ladder\|window \|token gap\|set-up" $out/call01_cell_t0.txt | cut -c1-600; tail -1 $out/call01_cell_t0.txt | cut -c1-1500
timeout -s KILL 900 python3 benchmark/tools/calls/pr59_with_metrics.py --workload $C --seed 5900000002 --seconds 51 --trace 1 > $out/call01_cell_t1.txt 2>&1
echo "cell traced: exit $?"; grep "logits vs\|roofline\|launches\|ticks in the window\|scope\|mamba2\|moe/\|attn/" $out/call01_cell_t1.txt | cut -c1-1200 | head -60; tail -1 $out/call01_cell_t1.txt | cut -c1-6000
timeout -s KILL 2400 python3 benchmark/tools/calls/pr59_faults.py 5900000059 2>&1 | grep -v Warn | tee $out/call01_faults.txt | grep "^seed\|^clean\|^seeding\|stopping\|Error\|error" | cut -c1-300
