#!/bin/bash
# PR 48, chip call 2 (1 chip): one traced run of the change in each of the six serving cells call 1 did not run.
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr48_call02_cells.sh
bash benchmark/tools/calls/pr48_cells.sh p48c2 4800000030 traced serve-mistral7b-longprompt-closed \
    serve-ouro-reason-closed8 serve-qwen3next-longchat-closed32 serve-lfm2-agent-closed128 \
    serve-moonlight-longdoc-closed64 serve-trinity-mixedlen-closed32
