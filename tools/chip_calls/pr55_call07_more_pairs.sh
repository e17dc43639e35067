#!/bin/bash
# PR 55, chip call 7 (1 chip): four more untraced pairs of the claimed cell on seeds of their own, the change = the
# committed files of the final tree (build/archive_check, made again after the last edit), the parent = build/parent.
CHANGE=/root/repo/build/archive_check SEEDS=4 TRACED=0 bash /root/repo/tools/chip_calls/pr55_cells.sh p55c7 5500000060 serve-qwen3next-longchat-closed32
exit 0
