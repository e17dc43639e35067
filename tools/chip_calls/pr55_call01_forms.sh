#!/bin/bash
# PR 55, chip call 1 (1 chip): the forms of the chunked delta rule's kernel at the Qwen3-Next cell's shape,
# microseconds a call (tools/chip_calls/pr55_candidates.py; the parent's module from build/parent).
out=/root/repo/chiprun_out/p55c1; mkdir -p $out
python3 tools/chip_calls/pr55_candidates.py parent null split split-hb8 split-hb2 split-full split-apart split-batched split-ssa \
    split-batched-ssa fused fused-hb8 rolled rolled-hb8 rolled-full-apart parent 2> $out/forms.err | tee $out/forms.jsonl | cut -c1-400
tail -5 $out/forms.err | cut -c1-300
exit 0
