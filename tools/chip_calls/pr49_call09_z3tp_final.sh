#!/bin/bash
# PR 49, chip call 9 (4 chips), from build/archive_check: the d128 cell, two untraced pairs (the second run of each
# side reads the warm set-up).
#   chiprun --chips 4 --timeout 2400 -- bash tools/chip_calls/pr49_call09_z3tp_final.sh
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash tools/chip_calls/pr49_cells.sh p49c9 4900000050 train-mistral7b-z3tp-s4k
grep -h "set-up" /root/repo/chiprun_out/p49c9/train-*.log
