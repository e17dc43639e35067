#!/bin/bash
# PR 47, chip call 4 (1 chip): the cells that are expected to move least: long-prompt (`mixed_ahead_pct` 50.6 at the
# parent; two untraced pairs), Moonlight and Trinity (98%) and the open-loop chat cell: an untraced and a traced pair each.
#   chiprun --timeout 3550 -- bash tools/chip_calls/pr47_call04.sh
SEEDS=2 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c4 4700000070 serve-mistral7b-longprompt-closed
SEEDS=1 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c4 4700000080 serve-mistral7b-chat-steady
SEEDS=1 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c4 4700000090 serve-moonlight-longdoc-closed64
SEEDS=1 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c4 4700000100 serve-trinity-mixedlen-closed32
