#!/bin/bash
# PR 53, the chip calls that run cells: the parent (build/parent = `git archive 626a5f3`) beside the change (the
# working tree, or CHANGE=<dir>), one process a run: benchmark/tools/calls/pr51_cells.sh as it stands (SEEDS untraced
# pairs a cell, the sides alternating, then TRACED traced pairs, the change first; logs under chiprun_out/<tag>/).
#   chiprun --timeout 3500 -- env SEEDS=6 TRACED=1 bash tools/chip_calls/pr53_cells.sh p53c3 5300000010 serve-lfm2-agent-closed128
exec bash "$(dirname "$0")/../../benchmark/tools/calls/pr51_cells.sh" "$@"
