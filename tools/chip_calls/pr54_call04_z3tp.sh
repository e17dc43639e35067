#!/bin/bash
# PR 54, chip call 4 (4 chips): `train-mistral7b-z3tp-s4k`, the committed files alone (build/archive_check) beside the
# parent (build/parent): two untraced pairs, the sides alternating.  At 128-wide heads the route keeps the [B, H, S, D]
# kernels and the lowered step is the parent's with the kernel bodies masked: nothing should move.
#   chiprun --chips 4 --timeout 1500 -- bash tools/chip_calls/pr54_call04_z3tp.sh
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash /root/repo/build/archive_check/benchmark/tools/calls/pr51_cells.sh p54c4 5400000200 train-mistral7b-z3tp-s4k
grep -h "set-up" /root/repo/chiprun_out/p54c4/*.log | cut -c1-300
