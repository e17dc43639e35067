#!/bin/bash
# PR 60, calls 3 and 4 (1 chip each): one untraced pair a cell in the six other cells that run `grouped_moe_ffn`, those
# most at risk first (k = 8, where the parent's float32 view was a whole tile and the decode tick is the cell: OLMoE,
# GLM-5; then the widest rows: LongCat; then Moonlight, LFM2, Trinity).  Every run compiles cold (~200 s), so three
# cells a call:
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr60_call03_others.sh p60c3 6000000030 serve-olmoe-chat-closed32 serve-glm5-longctx-closed16 serve-longcat-avturn-closed64
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr60_call03_others.sh p60c4 6000000040 serve-moonlight-longdoc-closed64 serve-lfm2-agent-closed128 serve-trinity-mixedlen-closed32
SEEDS=1 TRACED=0 exec bash /root/repo/tools/chip_calls/pr60_cells.sh "$@"
