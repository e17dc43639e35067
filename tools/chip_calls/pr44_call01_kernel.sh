#!/bin/bash
# PR 44, chip call 1 (1 chip): the tiled chunk read alone.  The accepted kernel (build/parent = `git archive 04c5a97`)
# at Trinity's shapes, four readings that split a call into live and skipped grid steps, and at every cell's shape;
# then this tree's kernel at the same, and its variants (key blocks a step 2 / 8; the mask on every step; the scale
# on the scores).  One process a tree: a process holds the chip.
#   chiprun --timeout 1500 -- bash tools/chip_calls/pr44_call01_kernel.sh
out=/root/repo/chiprun_out/p44c1; mkdir -p $out
b=tools/chip_calls/pr44_kernel_bench.py
timeout -s KILL 600 python $b --tree build/parent --out $out/parent.json all > $out/parent.log 2> $out/parent.err
echo "parent rc $?"; cat $out/parent.log
timeout -s KILL 500 python $b --out $out/change.json all > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -5 $out/change.err
timeout -s KILL 800 python $b --out $out/variants.json cells "{'kb': 2}" "{'kb': 8}" "{'mask_all': 1}" "{'scale_scores': 1}" > $out/variants.log 2> $out/variants.err
echo "variants rc $?"; cat $out/variants.log; tail -5 $out/variants.err
