#!/bin/bash
# PR 48, chip call 4 (1 chip): what tracing costs in the chat cell (`itl_p50_ms`), before and after the change: two
# rounds of parent untraced, parent traced, change traced, change untraced, a seed a round.
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr48_call04_cost_chat.sh
PAIRS=2 bash benchmark/tools/calls/pr48_cells.sh p48c4 4800000060 cost serve-mistral7b-chat-steady
