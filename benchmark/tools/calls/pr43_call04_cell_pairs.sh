#!/bin/bash
# PR 43, call 4 (1 chip): the cell on six seeds, tracing off, in one call (its spread), then the
# parent beside the change on the accepted cells that share the most of the touched host code.
TRACED=0 bash benchmark/tools/calls/pr43_call02_cell.sh p43c4 4300000411 4300000412 4300000413 4300000414 4300000415 4300000416
bash benchmark/tools/calls/pr43_call03_pairs.sh p43c4 4300000420 serve-mistral7b-longprompt-closed
PAIRS=1 bash benchmark/tools/calls/pr43_call03_pairs.sh p43c4 4300000430 serve-olmoe-chat-closed32 serve-trinity-mixedlen-closed32
