#!/bin/bash
# PR 45, chip call 1b (1 chip): the expanded latent read alone, the head loop and the dead steps' block.  Call 1 read
# the heads unrolled 19-22% faster than the loop compiled once: here the loop in groups of 2 / 4 / 8 heads (static
# offsets inside a group), `fori_loop`'s own unroll 2 / 4, and the steps past a tile's last live one naming the NEXT
# live step's block (its fetch then starts under the tile's last live step) in place of the last one's.
#   chiprun --timeout 900 -- bash tools/chip_calls/pr45_call01b_loop.sh
out=/root/repo/chiprun_out/p45c1b; mkdir -p $out
b=tools/chip_calls/pr45_kernel_bench.py
timeout -s KILL 800 python $b --out $out/change.json "{}" "{'group': 2}" "{'group': 4}" "{'group': 8}" "{'unroll': 2}" "{'unroll': 4}" "{'unroll': 1}" "{'next_fill': 1}" "{'next_fill': 1, 'unroll': 1}" "{'next_fill': 1, 'group': 4}" > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -5 $out/change.err
