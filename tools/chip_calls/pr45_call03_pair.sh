#!/bin/bash
# PR 45, chip call 3 (1 chip): the expanded latent read alone, a query block of TWO tiles of one chunk a grid step (256
# rows against each key block: half the buffer's stream, twice the rows a weight tile) beside the one-tile form, at key
# blocks of 2 / 4 / 8 table entries.
#   chiprun --timeout 900 -- bash tools/chip_calls/pr45_call03_pair.sh
out=/root/repo/chiprun_out/p45c3; mkdir -p $out
b=tools/chip_calls/pr45_kernel_bench.py
timeout -s KILL 800 python $b --out $out/change.json "{}" "{'pair': 1}" "{'pair': 1, 'kb': 2}" "{'pair': 1, 'kb': 8}" "{'kb': 8}" > $out/change.log 2> $out/change.err
echo "change rc $?"; cat $out/change.log; tail -5 $out/change.err
