#!/bin/bash
# PR 41, chip call 11 (1 chip): the two-index row insert (ragged_llama.insert_kv) in the setting that stalled (the moe phase's
# engines, 8-block pools, one after another in a process), the stalled form in a process with no Pallas kernel at all, and
# both forms' time at the cells' shapes.
OUT=/root/repo/chiprun_out/p41c11 bash tools/chip_calls/pr41_call10_hang.sh base grouped_twice plain_no_kernel
out=/root/repo/chiprun_out/p41c11; mkdir -p $out
timeout -s KILL 240 python3 tools/chip_calls/pr41_insert_probe.py $out/insert.json 2> $out/insert.err | grep "^INSERT"
