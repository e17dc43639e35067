#!/bin/bash
# PR 55, chip call 2 (1 chip): call 1 read the batched phase A at 581 us where the unrolled bodies read 1,075 and the
# parent 1,432: the forms that batch phase B over the heads of a chunk as well, and the one that applies the inverse late.
out=/root/repo/chiprun_out/p55c2; mkdir -p $out
python3 tools/chip_calls/pr55_candidates.py split-batched bb bb-hb8 bb-hb2 bb-apart bb-late bb-late-hb8 bb-ssa bb-late-ssa \
    split-late bb-full null parent 2> $out/forms.err | tee $out/forms.jsonl | cut -c1-400
tail -3 $out/forms.err | cut -c1-300
exit 0
