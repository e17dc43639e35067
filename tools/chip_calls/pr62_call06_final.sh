#!/bin/bash
# PR 62, the last chip call (4 chips): `train-mistral7b-z3tp-s4k`, the committed files alone (build/archive_check =
# `git archive $(git write-tree)`) beside the parent (build/parent = `git archive b465f97`): two untraced pairs, the
# sides alternating, a seed a pair, then a traced run of the change with its collectives by operation and scope and
# one layer's forward attention sublayer as it ran.
#   chiprun --chips 4 --timeout 2400 -- bash tools/chip_calls/pr62_call06_final.sh
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=1 TRACED_PARENT=0 \
    bash /root/repo/build/archive_check/tools/chip_calls/pr62_cells.sh p62c6 6200000060 train-mistral7b-z3tp-s4k
grep -h "set-up" /root/repo/chiprun_out/p62c6/*.log | cut -c1-200
