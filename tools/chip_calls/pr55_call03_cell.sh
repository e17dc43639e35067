#!/bin/bash
# PR 55, chip call 3 (1 chip): the committed body beside two more forms (the 16 -> 32 merge side by side; hb 8), then
# the claimed cell: two untraced pairs and one traced pair, the parent = build/parent (`git archive 8767a2b`).
out=/root/repo/chiprun_out/p55c3; mkdir -p $out
python3 tools/chip_calls/pr55_candidates.py committed bb-late-ssa-m16 committed-hb8 bb-late-ssa-m16-hb8 parent 2> $out/forms.err | tee $out/forms.jsonl | cut -c1-400
SEEDS=2 TRACED=1 bash tools/chip_calls/pr55_cells.sh p55c3 5500000010 serve-qwen3next-longchat-closed32
exit 0
