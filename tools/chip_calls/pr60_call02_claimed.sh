#!/bin/bash
# PR 60, call 2 (1 chip): the claimed cell, serve-qwen3next-longchat-closed32, three untraced pairs and one traced pair
# on one seed; then serve-granite4h-agent-closed128 (expected better, not claimed), two untraced pairs and one traced.
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr60_call02_claimed.sh
SEEDS=3 TRACED=1 bash /root/repo/tools/chip_calls/pr60_cells.sh p60c2 6000000010 serve-qwen3next-longchat-closed32
SEEDS=2 TRACED=1 bash /root/repo/tools/chip_calls/pr60_cells.sh p60c2 2200000040 serve-granite4h-agent-closed128
exit 0
