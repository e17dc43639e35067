#!/bin/bash
# PR 44, chip calls 2..: this tree's tiled chunk read at every cell's shape under the variants named on the command
# line (one process: a process holds the chip), checked against the XLA read.
#   chiprun --timeout 1500 -- bash tools/chip_calls/pr44_call02_variants.sh p44c2 "{}" "{'kb': 8}" ...
out=/root/repo/chiprun_out/$1; shift; mkdir -p $out
timeout -s KILL 1300 python tools/chip_calls/pr44_kernel_bench.py --out $out/variants.json cells "$@" > $out/variants.log 2> $out/variants.err
echo "variants rc $?"; cat $out/variants.log; grep -v Warn $out/variants.err | tail -5
