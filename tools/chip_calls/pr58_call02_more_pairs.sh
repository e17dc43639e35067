#!/bin/bash
# PR 58, chip call 2 (1 chip): more pairs on the same two trees as call 1 (build/archive_check, build/parent): the
# claimed cell, two untraced pairs; the control `serve-qwen3next-longchat-closed32`, one untraced pair.
export CHANGE=/root/repo/build/archive_check
SEEDS=2 TRACED=0 bash /root/repo/tools/chip_calls/pr58_cells.sh p58c2 5800000070 serve-olmohybrid-evalgen-closed128
SEEDS=1 TRACED=0 bash /root/repo/tools/chip_calls/pr58_cells.sh p58c2 2200000080 serve-qwen3next-longchat-closed32
exit 0
