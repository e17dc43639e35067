#!/bin/bash
# PR 49, chip call 7 (1 chip): the claimed cell again with `_fwd` / `_bwd` jitted (the kernels traced once a process):
# two untraced pairs, for the set-up and the rate.
#   chiprun --timeout 2400 -- bash tools/chip_calls/pr49_call07_gpt2_jitted.sh
SEEDS=2 TRACED=0 bash tools/chip_calls/pr49_cells.sh p49c7 4900000040 train-gpt2large-d64-s1k
grep -h "set-up" /root/repo/chiprun_out/p49c7/*.log
