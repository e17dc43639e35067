#!/bin/bash
# PR 59, call 5: the guard and the committed files.  build/archive_check is
# `git archive $(git write-tree)` of the change, build/parent the parent
# with this PR's benchmark laid over it.  serve-jamba2-reason-closed256
# shares the state slots, the convolution with a bias and the position-free
# attention block (which gained a static branch on cfg.query_scale) with the
# new model: parent, change, change, parent untraced; one traced run of it
# on the parent under this PR's benchmark files, as the driver makes them;
# then the new cell from the committed files, traced, as the driver runs it.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr59_overlay.sh
test -d build/archive_check/deepspeed_tpu || exit 2
out=$PWD/chiprun_out/pr59; mkdir -p $out
J=serve-jamba2-reason-closed256
run() { # side cell seed trace
  local dir=build/archive_check; [ "$1" = parent ] && dir=build/parent
  (cd $dir && timeout -s KILL 900 python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4) > $out/call05_$1_$2_$3_t$4.txt 2>&1
  echo "$1 $2 seed $3 trace $4: $(tail -1 $out/call05_$1_$2_$3_t$4.txt | cut -c1-2600)"
}
run parent $J 5900000011 0; run change $J 5900000011 0; run change $J 5900000012 0; run parent $J 5900000012 0
run parent $J 5900000013 1
run change serve-granite4h-agent-closed128 5900000031 1
