#!/bin/bash
# PR 49, chip call 4 (1 chip): the claimed cell, two untraced pairs and one traced pair, parent and change.
#   chiprun --timeout 3000 -- bash tools/chip_calls/pr49_call04_gpt2.sh
SEEDS=2 TRACED=1 bash tools/chip_calls/pr49_cells.sh p49c4 4900000010 train-gpt2large-d64-s1k
