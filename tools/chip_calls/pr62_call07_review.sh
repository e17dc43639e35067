#!/bin/bash
# PR 62, the review round's chip call (4 chips): every weight gradient one product over the rank's tokens again.
# `train-mistral7b-z3tp-s4k`, the committed files alone (build/archive_check = `git archive $(git write-tree)`) beside
# the parent (build/parent = `git archive b465f97`): one untraced pair, the change untraced once more on a seed of its
# own, the change traced with its collectives by operation and scope; then the sweep that sets
# `tensor_overlap.MIN_CHUNK_ROWS`: the two sublayers at chunks of 256 / 512 / 1,024 / 2,048 rows, GSPMD against the ring.
#   chiprun --chips 4 --timeout 1500 -- bash tools/chip_calls/pr62_call07_review.sh
check=/root/repo/build/archive_check; out=/root/repo/chiprun_out/p62c7
CHANGE=$check SEEDS=1 TRACED=0 bash $check/tools/chip_calls/pr62_cells.sh p62c7 6200000070 train-mistral7b-z3tp-s4k
CHANGE=$check SEEDS=0 TRACED=1 TRACED_PARENT=0 bash $check/tools/chip_calls/pr62_cells.sh p62c7 6200000071 train-mistral7b-z3tp-s4k
( cd $check && python3 benchmark/run.py --workload train-mistral7b-z3tp-s4k --seed 6200000073 --seconds 51 --trace 0 \
    > $out/train-mistral7b-z3tp-s4k.change.s6200000073.t0.log 2> $out/train-mistral7b-z3tp-s4k.change.s6200000073.t0.err )
echo "change seed 6200000073 trace 0: rc $? $(tail -1 $out/train-mistral7b-z3tp-s4k.change.s6200000073.t0.log | cut -c1-600)"
grep -h "set-up" $out/*.log | cut -c1-200
( cd $check && python3 tools/chip_calls/pr62_tp_ring.py --sublayer 512 1024 2048 4096 2>&1 | grep -v "^\[" | tail -20 )
mkdir -p $out && cp $check/chiprun_out/pr62/sublayer_sweep.json $out/ 2>/dev/null
exit 0
