#!/bin/bash
# PR 50, call 1 (1 chip): (a) the parent (build/parent = `git archive
# d8a2221` under this PR's BENCHMARK.json and benchmark/, pr50_overlay.sh) on
# the new cell: it has to fail at once; (b) the three steps of the sparse
# read alone at the published widths (pr50_call01_steps.py); (c) the check's
# reading on the clean program and on each fault (pr50_faults.py), one seed.
#   bash benchmark/tools/calls/pr50_call01_first.sh <seed>
root=$(cd "$(dirname "$0")/../../.." && pwd); cd "$root"
out=$root/chiprun_out/pr50; mkdir -p $out
filter() { grep -v "cpu_aot_loader\|hugepage\|warnings.warn"; }
t0=$(date +%s)
( cd build/parent && timeout 300 python3 benchmark/run.py --workload serve-glm5-longctx-closed16 \
    --seed $1 --seconds 51 --trace 0 > $out/call01_parent.log 2> $out/call01_parent.err )
echo "parent on the new cell: rc $? after $(( $(date +%s) - t0 )) s: $(tail -2 $out/call01_parent.err | cut -c1-400)"
python3 benchmark/tools/calls/pr50_call01_steps.py 2>&1 | filter | tee $out/call01_steps.log
python3 benchmark/tools/calls/pr50_faults.py ${ONLY:+ONLY=$ONLY} $1 2>&1 | filter | tee $out/call01_faults.log
