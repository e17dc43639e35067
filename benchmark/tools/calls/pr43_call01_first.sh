#!/bin/bash
# PR 43, call 1 (1 chip): the check's reading on the clean program and on
# each fault (two seeds), the clean program's and the bf16 stream's spread
# (four more seeds), then the looped against the unrolled step programs.
set -x
mkdir -p chiprun_out/pr43
python3 benchmark/tools/calls/pr43_faults.py 4300000043 2147483659 2>&1 | grep -v cpu_aot_loader | tee chiprun_out/pr43/call01_faults.log
python3 benchmark/tools/calls/pr43_faults.py ONLY=clean,bf16_stream 11 3000000022 33 2147483999 2>&1 | grep -v cpu_aot_loader | tee chiprun_out/pr43/call01_spread.log
python3 benchmark/tools/calls/pr43_loop_vs_unrolled.py looped unrolled 2>&1 | grep -v cpu_aot_loader | tee chiprun_out/pr43/call01_loop.log
