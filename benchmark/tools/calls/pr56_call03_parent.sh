#!/bin/bash
# PR 56, call 3: the parent (build/parent, this PR's benchmark laid over it)
# on the new cell: it has to fail at once and by name; then the Qwen3-Next
# cell, which shares gdn_step / gdn_chunk / the mixer with the new model,
# parent, change, change, parent untraced and one traced run a side.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr56_overlay.sh
out=$PWD/chiprun_out/pr56; mkdir -p $out
Q=serve-qwen3next-longchat-closed32
t0=$(date +%s)
(cd build/parent && timeout 600 python3 benchmark/run.py --workload serve-olmohybrid-evalgen-closed128 --seed 5600000003 --seconds 51 --trace 0) > $out/call03_parent_newcell.txt 2>&1
echo "parent on the new cell: exit $? after $(( $(date +%s) - t0 )) s"; tail -4 $out/call03_parent_newcell.txt | cut -c1-400
run() { # side seed trace
  local dir=.; [ "$1" = parent ] && dir=build/parent
  (cd $dir && python3 benchmark/run.py --workload $Q --seed $2 --seconds 51 --trace $3) > $out/call03_q_$1_$2_t$3.txt 2>&1
  echo "$1 seed $2 trace $3: $(tail -1 $out/call03_q_$1_$2_t$3.txt | cut -c1-2500)"
}
run parent 5600000011 0; run change 5600000011 0; run change 5600000012 0; run parent 5600000012 0
run parent 5600000013 1; run change 5600000013 1
