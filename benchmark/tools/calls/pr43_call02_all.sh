#!/bin/bash
# PR 43, call 2 (1 chip): the cell (pr43_call02_cell.sh), then the clean check's gap on 24 more seeds.
bash benchmark/tools/calls/pr43_call02_cell.sh p43c2 4300000201 4300000202
python3 benchmark/tools/calls/pr43_faults.py ONLY=clean 101 202 303 404 505 606 707 808 909 1010 2147483001 2147483002 \
    2147483003 2147483004 3000000005 3000000006 3000000007 3000000008 4000000009 4000000010 4000000011 4000000012 77 88 \
    2>&1 | grep -v "cpu_aot_loader\|INFO" | tee /root/repo/chiprun_out/p43c2/spread24.log
