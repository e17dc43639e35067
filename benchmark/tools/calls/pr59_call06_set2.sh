#!/bin/bash
# PR 59, call 6: a second set of six untraced runs of the new cell, six new
# seeds (two above 2**31), for the spread of its end-to-end metrics.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr59; mkdir -p $out
C=serve-granite4h-agent-closed128
for seed in 5900000041 2147483951 5900000043 3100000159 5900000045 4294967279; do
  timeout -s KILL 900 python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/call06_cell_$seed.txt 2>&1
  echo "seed $seed: $(grep 'logits vs' $out/call06_cell_$seed.txt | sed 's/.*= //') $(tail -1 $out/call06_cell_$seed.txt | cut -c1-500)"
done
