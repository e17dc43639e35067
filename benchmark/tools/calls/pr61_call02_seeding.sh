#!/bin/bash
# PR 61, call 2 (first half): what o_proj's scale (ATTN_OUT of
# families/dots3_note.py) lets the check see: the faults call 1 left unread
# (the process died in window_reads_global) at 0.3, then the clean program,
# the skipped indexer, the fault that read just under the limit and the
# low-precision control at 0.5, the first two at 0.7, with the clean program
# on more seeds.  The second half is pr61_call03_cell.sh.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr61; mkdir -p $out
F="timeout -s KILL 1200 python3 benchmark/tools/calls/pr61_faults.py"
show() { grep -v Warn | tee -a $out/call02_seeding.txt | grep "^seed\|^clean\|^seeding\|Error\|error" | cut -c1-300; }
$F ONLY=indexer_skipped,band_released_early,reference_low_precision 6100000061 2>&1 | show
$F ATTN_OUT=0.5 ONLY=clean,indexer_skipped,gate_per_value,reference_low_precision 6100000061 2>&1 | show
$F ATTN_OUT=0.5 ONLY=clean 6100000062 3100000063 2>&1 | show
$F ATTN_OUT=0.7 ONLY=clean,indexer_skipped 6100000061 2>&1 | show
$F ATTN_OUT=0.7 ONLY=clean 6100000062 2>&1 | show
