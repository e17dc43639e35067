#!/bin/bash
# PR 59, call 4: the parent (build/parent, this PR's benchmark laid over
# it) on the new cell: it has to fail at once and by name; then the new cell
# six times, a seed a run (two above 2**31), for the spread of its
# end-to-end metrics against half their bounds; then one traced run with
# the five waiting per-layer metrics.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr59_overlay.sh
out=$PWD/chiprun_out/pr59; mkdir -p $out
C=serve-granite4h-agent-closed128
t0=$(date +%s)
(cd build/parent && timeout 600 python3 benchmark/run.py --workload $C --seed 5900000003 --seconds 51 --trace 0) > $out/call04_parent_newcell.txt 2>&1
echo "parent on the new cell: exit $? after $(( $(date +%s) - t0 )) s"; tail -3 $out/call04_parent_newcell.txt | cut -c1-300
for seed in 5900000021 2147483747 5900000023 3100000059 5900000025 4294967291; do
  timeout -s KILL 900 python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/call04_cell_$seed.txt 2>&1
  echo "seed $seed: $(grep 'logits vs' $out/call04_cell_$seed.txt | sed 's/.*= //') $(tail -1 $out/call04_cell_$seed.txt | cut -c1-500)"
done
timeout -s KILL 900 python3 benchmark/tools/calls/pr59_with_metrics.py --workload $C --seed 5900000027 --seconds 51 --trace 1 > $out/call04_cell_t1.txt 2>&1
echo "traced: exit $?"; grep "logits vs\|device ms per\|roofline:" $out/call04_cell_t1.txt | cut -c1-1500; tail -1 $out/call04_cell_t1.txt | cut -c1-7000
