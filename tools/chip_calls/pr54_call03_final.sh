#!/bin/bash
# PR 54, chip call 3 (1 chip): the committed files alone (build/archive_check = `git archive $(git write-tree)` of the
# final tree) beside the parent (build/parent = `git archive 444c052`).  chip_smoke.py's `train` and `kernels` phases in
# a process of its own under a limit (the self-test with `flash_train`, the folded cases and `folded_quad_d32` in it),
# then `train-gpt2large-d64-s1k`: two untraced pairs and one traced pair, the sides alternating.  The cell's family
# changes with this PR (the head size picks the folded kernels), so the change should read what call 1 read for `folded`.
#   chiprun --timeout 2400 -- bash tools/chip_calls/pr54_call03_final.sh
out=/root/repo/chiprun_out/p54c3; mkdir -p $out
a=/root/repo/build/archive_check
( cd $a && timeout -s KILL 900 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('train', 'kernels')); json.dump(s, open('$out/chip_smoke.json', 'w'), indent=1, default=str)" > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke train+kernels: rc $? $(tail -2 $out/chip_smoke.log | cut -c1-700)"
CHANGE=$a SEEDS=2 TRACED=1 bash $a/benchmark/tools/calls/pr51_cells.sh p54c3 5400000100 train-gpt2large-d64-s1k
grep -h "attention kernels\|set-up" $out/train-gpt2large-d64-s1k.*.log | cut -c1-300
