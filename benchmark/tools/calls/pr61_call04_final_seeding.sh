#!/bin/bash
# PR 61, call 4: at the seeding the file now has (ATTN_OUT 0.7): the whole
# fault table at one seed, then the new cell six times more, a seed a run
# (two above 2**31): the second set for the spread, and `correct` on six
# seeds at the final seeding.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr61; mkdir -p $out
timeout -s KILL 1800 python3 benchmark/tools/calls/pr61_faults.py 6100000071 2>&1 | grep -v Warn | tee $out/call04_faults.txt | grep "^seed\|^clean\|^seeding\|Error\|error" | cut -c1-300
C=serve-dots3-notes-closed48
for seed in 6100000041 2500000043 6100000045 3300000047 6100000049 4100000051; do
  timeout -s KILL 1200 python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/call04_cell_$seed.txt 2>&1
  echo "seed $seed: $(grep 'logits vs' $out/call04_cell_$seed.txt | sed 's/.*= //') $(grep -o 'tick p50 [0-9.]* ms' $out/call04_cell_$seed.txt) $(tail -1 $out/call04_cell_$seed.txt | cut -c1-330)"
done
