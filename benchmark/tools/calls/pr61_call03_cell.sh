#!/bin/bash
# PR 61, call 3: the parent (build/parent, this PR's benchmark laid over
# it) on the new cell: it has to fail at once and by name; then the new cell
# six times, a seed a run (two above 2**31), for the spread of its
# end-to-end metrics against half their bounds; then one traced run with
# the four waiting per-layer metrics.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr61_overlay.sh
out=$PWD/chiprun_out/pr61; mkdir -p $out
C=serve-dots3-notes-closed48
t0=$(date +%s)
(cd build/parent && timeout 600 python3 benchmark/run.py --workload $C --seed 6100000003 --seconds 51 --trace 0) > $out/call03_parent_newcell.txt 2>&1
echo "parent on the new cell: exit $? after $(( $(date +%s) - t0 )) s"; tail -3 $out/call03_parent_newcell.txt | cut -c1-300
for seed in ${SEEDS:-6100000021 2147483747 6100000023 3100000061 6100000025 4294967291}; do
  timeout -s KILL 1200 python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/call03_cell_$seed.txt 2>&1
  echo "seed $seed: $(grep 'logits vs' $out/call03_cell_$seed.txt | sed 's/.*= //') $(grep -o 'tick p50 [0-9.]* ms' $out/call03_cell_$seed.txt) $(tail -1 $out/call03_cell_$seed.txt | cut -c1-400)"
  python3 - bench_out/$C/window_seed$seed.json <<'PY'
import json, statistics, sys
# what tpot_p50_ms would read in this run: (last - first token) / (tokens - 1)
# over the window's finished requests (the side file's records)
rec = json.load(open(sys.argv[1]))
tpot = [1e3 * (last - first) / (n - 1) for _p, want, _d, _s, first, last, n, inw
        in rec["requests"] if inw and first is not None and n >= want and n > 1]
print(f"  tpot p50 {statistics.median(tpot):.3f} ms over {len(tpot)} requests")
PY
done
timeout -s KILL 1200 python3 benchmark/tools/calls/pr61_with_metrics.py --workload $C --seed 6100000027 --seconds 51 --trace 1 > $out/call03_cell_t1.txt 2>&1
echo "traced: exit $?"; grep "logits vs\|device ms per\|roofline:\|launches: program" $out/call03_cell_t1.txt | cut -c1-1500; tail -1 $out/call03_cell_t1.txt | cut -c1-7000
