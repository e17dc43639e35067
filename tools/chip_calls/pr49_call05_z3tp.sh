#!/bin/bash
# PR 49, chip call 5 (4 chips): the d128 cell under shard_map, one untraced pair and one traced pair, parent and change.
#   chiprun --chips 4 --timeout 2400 -- bash tools/chip_calls/pr49_call05_z3tp.sh
SEEDS=1 TRACED=1 bash tools/chip_calls/pr49_cells.sh p49c5 4900000020 train-mistral7b-z3tp-s4k
