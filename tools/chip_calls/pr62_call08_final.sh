#!/bin/bash
# PR 62, the last chip call (4 chips): the final tree's committed files alone (build/archive_check =
# `git archive $(git write-tree)`; since call 7: `MIN_CHUNK_ROWS` 512, which the cell's 2,048-row chunks do not
# meet, and texts) beside the parent (build/parent = `git archive b465f97`): one untraced pair on a seed of its own.
#   chiprun --chips 4 --timeout 900 -- bash tools/chip_calls/pr62_call08_final.sh
CHANGE=/root/repo/build/archive_check SEEDS=1 TRACED=0 \
    bash /root/repo/build/archive_check/tools/chip_calls/pr62_cells.sh p62c8 6200000080 train-mistral7b-z3tp-s4k
grep -h "set-up" /root/repo/chiprun_out/p62c8/*.log | cut -c1-200
