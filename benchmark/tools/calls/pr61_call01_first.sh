#!/bin/bash
# PR 61, call 1: the new cell once untraced and once traced with the four
# waiting metrics; then the fault table of pr61_faults.py at one seed (it
# prints a line a variant).
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr61; mkdir -p $out
C=serve-dots3-notes-closed48
timeout -s KILL 1500 python3 benchmark/run.py --workload $C --seed 6100000001 --seconds 51 --trace 0 > $out/call01_cell_t0.txt 2>&1
echo "cell untraced: exit $?"; grep "logits vs\|resident\|shape ladder\|window \|token gap\|set-up\|Error\|error" $out/call01_cell_t0.txt | cut -c1-700; tail -1 $out/call01_cell_t0.txt | cut -c1-1500
timeout -s KILL 1500 python3 benchmark/tools/calls/pr61_with_metrics.py --workload $C --seed 6100000002 --seconds 51 --trace 1 > $out/call01_cell_t1.txt 2>&1
echo "cell traced: exit $?"; grep "logits vs\|roofline\|launches\|ticks in the window\|device ms per\|Error\|error" $out/call01_cell_t1.txt | cut -c1-1200 | head -60; tail -1 $out/call01_cell_t1.txt | cut -c1-7000
timeout -s KILL 2400 python3 benchmark/tools/calls/pr61_faults.py 6100000061 2>&1 | grep -v Warn | tee $out/call01_faults.txt | grep "^seed\|^clean\|^seeding\|stopping\|Error\|error" | cut -c1-300
