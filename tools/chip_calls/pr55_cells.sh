#!/bin/bash
# PR 55, the chip calls that run cells: the parent (build/parent = `git archive 8767a2b`) beside the change (the
# working tree, or CHANGE=<dir>), one process a run: benchmark/tools/calls/pr51_cells.sh as it stands (SEEDS untraced
# pairs a cell, the sides alternating, then TRACED traced pairs, the change first; logs under chiprun_out/<tag>/).
#   chiprun --timeout 3500 -- env SEEDS=6 TRACED=1 bash tools/chip_calls/pr55_cells.sh p55c4 5500000020 serve-qwen3next-longchat-closed32
exec bash "$(dirname "$0")/../../benchmark/tools/calls/pr51_cells.sh" "$@"
