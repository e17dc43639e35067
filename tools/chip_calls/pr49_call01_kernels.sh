#!/bin/bash
# PR 49, chip call 1 (1 chip): the three bshd flash kernels alone at the two training cells' shapes.  The accepted
# kernels (build/parent = `git archive 82eb71a`) at their tiles of 512 x 1024 and at smaller grid tiles (form (b)),
# then this tree's walk (form (a)) at several sub-block sizes.  One process a tree: a process holds the chip.
#   chiprun --timeout 1700 -- bash tools/chip_calls/pr49_call01_kernels.sh
out=/root/repo/chiprun_out/p49c1; mkdir -p $out
b=tools/chip_calls/pr49_kernel_bench.py
timeout -s KILL 700 python $b --tree build/parent --out $out/parent.json "{}" "{'DEFAULT_BLOCK_K': 512}" \
    "{'DEFAULT_BLOCK_Q': 256, 'DEFAULT_BLOCK_K': 512}" "{'DEFAULT_BLOCK_Q': 256, 'DEFAULT_BLOCK_K': 256}" \
    > $out/parent.log 2> $out/parent.err
echo "parent rc $?"; cat $out/parent.log; tail -3 $out/parent.err
timeout -s KILL 900 python $b --out $out/change.json "{}" "{'SUB_BLOCK_Q': 512}" "{'SUB_BLOCK_K': 512}" \
    "{'SUB_BLOCK_Q': 512, 'SUB_BLOCK_K': 512}" "{'SUB_BLOCK_K': 128}" "{'SUB_BLOCK_Q': 128, 'SUB_BLOCK_K': 128}" \
    "{'SUB_BLOCK_Q': 256, 'SUB_BLOCK_K': 256, 'DEFAULT_BLOCK_Q': 1024}" \
    > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -3 $out/change.err
