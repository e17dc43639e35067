#!/bin/bash
# PR 43, call 3 (1 chip): the check's reading with the post-branch norms seeded N(0, 0.1^2)
# (benchmark/families/ouro.py::POST_NORM_STD): every variant on two seeds, the clean program on 30
# more, and on 12 at 0.25; then the cell, tracing off, on six seeds (its spread).
out=/root/repo/chiprun_out/p43c3; mkdir -p $out
f="python3 benchmark/tools/calls/pr43_faults.py"
$f 4300000301 2147483302 2>&1 | grep -v "cpu_aot_loader\|INFO" | tee $out/faults.log
$f ONLY=clean 101 202 303 404 505 606 707 808 909 1010 2147483001 2147483002 2147483003 2147483004 3000000005 \
    3000000006 3000000007 3000000008 4000000009 4000000010 4000000011 4000000012 77 88 11 3000000022 33 2147483999 \
    4300000043 2147483659 2>&1 | grep -v "cpu_aot_loader\|INFO" | tee $out/spread30.log
$f POST_NORM_STD=0.25 ONLY=clean 101 303 606 707 2147483002 3000000005 3000000006 3000000007 4000000009 4000000010 \
    4000000011 33 2>&1 | grep -v "cpu_aot_loader\|INFO" | tee $out/spread12_025.log
TRACED=0 bash benchmark/tools/calls/pr43_call02_cell.sh p43c3 4300000311 4300000312 4300000313 4300000314 4300000315 4300000316
