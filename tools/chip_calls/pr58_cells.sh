#!/bin/bash
# PR 58, the chip calls that run cells: the parent (build/parent = `git archive 42d22b6`) beside the change (the
# working tree, or CHANGE=<dir>), one process a run: benchmark/tools/calls/pr51_cells.sh as it stands (SEEDS untraced
# pairs a cell, the sides alternating, then TRACED traced pairs, the change first; logs under chiprun_out/<tag>/).
#   chiprun --timeout 3500 -- env SEEDS=2 TRACED=1 bash tools/chip_calls/pr58_cells.sh p58c1 5800000010 serve-olmohybrid-evalgen-closed128
exec bash "$(dirname "$0")/../../benchmark/tools/calls/pr51_cells.sh" "$@"
