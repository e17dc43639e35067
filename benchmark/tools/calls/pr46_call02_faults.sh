#!/bin/bash
# PR 46, call 2 (1 chip): both scan kernels alone at the published shapes
# (a pool a layer, donated), then the check's reading on the clean program,
# on each fault, on the bf16-state reading and on the low-precision control.
#   bash benchmark/tools/calls/pr46_call02_faults.sh [NAME=value ...] <seed> [<seed> ...]
mkdir -p chiprun_out/pr46
python3 benchmark/tools/calls/pr46_call01_kernels.py 2>&1 | grep -v "cpu_aot_loader\|hugepage\|warnings.warn" | tee chiprun_out/pr46/call02_kernels.log
python3 benchmark/tools/calls/pr46_faults.py "$@" 2>&1 | grep -v "cpu_aot_loader\|hugepage\|warnings.warn" | tee chiprun_out/pr46/call02_faults.log
