#!/bin/bash
# PR 45, chip call 1 (1 chip): the expanded latent read alone.  The accepted kernel (build/parent = `git archive 045f6ac`)
# at the cell's shape (a 1,024-token chunk from 0 / 2,048 / 6,144 over a 60-entry table), then this tree's kernel at the
# same, and its variants (key blocks a step 1 / 2 / 8; one dot over a concatenated key; the mask on every step; column
# statistics; the heads unrolled).  One process a tree: a process holds the chip.
#   chiprun --timeout 1500 -- bash tools/chip_calls/pr45_call01_kernel.sh
out=/root/repo/chiprun_out/p45c1; mkdir -p $out
b=tools/chip_calls/pr45_kernel_bench.py
timeout -s KILL 400 python $b --tree build/parent --out $out/parent.json > $out/parent.log 2> $out/parent.err
echo "parent rc $?"; cat $out/parent.log; tail -3 $out/parent.err
timeout -s KILL 900 python $b --out $out/change.json "{}" "{'kb': 1}" "{'kb': 2}" "{'kb': 8}" "{'concat': 1}" "{'mask_all': 1}" "{'cols': 1}" "{'unroll': 1}" > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -5 $out/change.err
