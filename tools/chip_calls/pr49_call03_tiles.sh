#!/bin/bash
# PR 49, chip call 3 (1 chip): the three kernels alone with the index maps of the steps outside the band held (this
# tree), at q-tiles of 512 and of 1024 rows and k-tiles of 1024 and 2048 keys, beside the accepted kernels.
#   chiprun --timeout 1500 -- bash tools/chip_calls/pr49_call03_tiles.sh
out=/root/repo/chiprun_out/p49c3; mkdir -p $out
b=tools/chip_calls/pr49_kernel_bench.py
timeout -s KILL 300 python $b --tree build/parent --out $out/parent.json "{}" > $out/parent.log 2> $out/parent.err
echo "parent rc $?"; cat $out/parent.log; tail -3 $out/parent.err
timeout -s KILL 900 python $b --out $out/change.json "{}" "{'DEFAULT_BLOCK_Q': 1024}" \
    "{'DEFAULT_BLOCK_Q': 1024, 'SUB_BLOCK_Q': 512}" "{'DEFAULT_BLOCK_Q': 1024, 'DEFAULT_BLOCK_K': 2048}" \
    "{'DEFAULT_BLOCK_Q': 1024, 'SUB_BLOCK_K': 512}" "{'DEFAULT_BLOCK_Q': 256}" \
    > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -3 $out/change.err
