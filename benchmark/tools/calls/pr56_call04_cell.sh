#!/bin/bash
# PR 56, call 4: the new cell six times, a seed a run (two above 2**31), for
# the spread of its end-to-end metrics against half their bounds; then the
# faults table of benchmark/tools/calls/pr56_faults.py at one seed and the
# clean check at two more.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr56; mkdir -p $out
C=serve-olmohybrid-evalgen-closed128
for seed in 5600000021 2147483747 5600000023 3100000056 5600000025 4294967291; do
  python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/call04_cell_$seed.txt 2>&1
  echo "seed $seed: $(grep 'logits vs' $out/call04_cell_$seed.txt | sed 's/.*= //') $(tail -1 $out/call04_cell_$seed.txt | cut -c1-400)"
done
timeout -s KILL 1500 python3 benchmark/tools/calls/pr56_faults.py 5600000056 2>&1 | grep -v Warn | tee $out/call04_faults.txt | grep "^seed\|^clean"
timeout -s KILL 600 python3 benchmark/tools/calls/pr56_faults.py ONLY=clean 2147483777 5600000058 2>&1 | tee -a $out/call04_faults.txt | grep "^seed\|^clean"
