#!/bin/bash
# PR 47, chip call 2 (1 chip): four more untraced pairs of the claimed cell (two seeds over 2**31), then OLMoE and Ouro
# (the two cells whose `mixed_ahead_pct` read 3.4 and 0.0): an untraced pair (two for OLMoE) and a traced pair each.
#   chiprun --timeout 3550 -- bash tools/chip_calls/pr47_call02.sh
SEEDS=2 TRACED=0 bash tools/chip_calls/pr47_cells.sh p47c2 4700000020 serve-jamba2-reason-closed256
SEEDS=2 TRACED=0 bash tools/chip_calls/pr47_cells.sh p47c2 2147483990 serve-jamba2-reason-closed256
SEEDS=2 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c2 4700000030 serve-olmoe-chat-closed32
SEEDS=1 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c2 4700000040 serve-ouro-reason-closed8
