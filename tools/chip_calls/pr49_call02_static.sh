#!/bin/bash
# PR 49, chip call 2 (1 chip): the three kernels alone, a STATIC body a crossed displacement (this tree) beside the
# accepted kernels (build/parent), at several sub-block sizes.
#   chiprun --timeout 1500 -- bash tools/chip_calls/pr49_call02_static.sh
out=/root/repo/chiprun_out/p49c2; mkdir -p $out
b=tools/chip_calls/pr49_kernel_bench.py
timeout -s KILL 300 python $b --tree build/parent --out $out/parent.json "{}" > $out/parent.log 2> $out/parent.err
echo "parent rc $?"; cat $out/parent.log; tail -3 $out/parent.err
timeout -s KILL 900 python $b --out $out/change.json "{}" "{'SUB_BLOCK_Q': 512}" "{'SUB_BLOCK_K': 512}" \
    "{'SUB_BLOCK_Q': 128}" "{'SUB_BLOCK_Q': 128, 'SUB_BLOCK_K': 128}" "{'SUB_BLOCK_Q': 512, 'SUB_BLOCK_K': 512}" \
    > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -3 $out/change.err
