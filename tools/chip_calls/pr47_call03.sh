#!/bin/bash
# PR 47, chip call 3 (1 chip): Qwen3-Next and LFM2 (`mixed_ahead_pct` 52.4 and 87.6 at the parent): two untraced pairs
# and a traced pair each.
#   chiprun --timeout 3550 -- bash tools/chip_calls/pr47_call03.sh
SEEDS=2 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c3 4700000050 serve-qwen3next-longchat-closed32
SEEDS=2 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c3 4700000060 serve-lfm2-agent-closed128
