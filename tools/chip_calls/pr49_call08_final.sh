#!/bin/bash
# PR 49, chip call 8 (1 chip), from build/archive_check = `git archive $(git write-tree)` of the final tree: the
# committed files alone.  chip_smoke.py's `train` and `kernels` phases (the self-test with the two training cells'
# shapes in it), the three kernels alone beside the accepted ones, then the claimed cell: two untraced pairs and one
# traced pair, parent and archive tree.
#   chiprun --timeout 3400 -- bash tools/chip_calls/pr49_call08_final.sh
out=/root/repo/chiprun_out/p49c8; mkdir -p $out
a=/root/repo/build/archive_check
( cd $a && timeout -s KILL 900 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('train', 'kernels')); json.dump(s, open('$out/chip_smoke.json', 'w'), indent=1, default=str)" > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke train+kernels: rc $? $(tail -2 $out/chip_smoke.log | cut -c1-700)"
b=$a/tools/chip_calls/pr49_kernel_bench.py
( cd $a && timeout -s KILL 300 python $b --tree /root/repo/build/parent --out $out/parent.json "{}" > $out/parent.log 2> $out/parent.err )
echo "parent rc $?"; cat $out/parent.log
( cd $a && timeout -s KILL 300 python $b --tree $a --out $out/change.json "{}" > $out/change.log 2> $out/change.err )
echo "change rc $?"; cat $out/change.log; tail -3 $out/change.err
CHANGE=$a SEEDS=2 TRACED=1 bash tools/chip_calls/pr49_cells.sh p49c8 2147484200 train-gpt2large-d64-s1k
grep -h "set-up" $out/train-*.log
