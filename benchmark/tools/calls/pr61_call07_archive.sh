#!/bin/bash
# PR 61, call 7: the committed files.  build/archive_check is `git archive
# $(git write-tree)` of the change, build/parent the parent with this PR's
# benchmark laid over it.  The new cell from the committed files, traced, as
# the driver runs it (`benchmark/run.py`, the accepted metrics alone); one
# old cell that shares the touched code traced on the PARENT under this
# PR's benchmark files, as the driver makes its traced runs; the new cell
# once more untraced from the committed files.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr61_overlay.sh
test -d build/archive_check/deepspeed_tpu || exit 2
out=$PWD/chiprun_out/pr61; mkdir -p $out
run() { # side cell seed trace
  local dir=build/archive_check; [ "$1" = parent ] && dir=build/parent
  (cd $dir && timeout -s KILL 1200 python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4) > $out/call07_$1_$2_$3_t$4.txt 2>&1
  echo "$1 $2 seed $3 trace $4: exit $? $(grep 'logits vs' $out/call07_$1_$2_$3_t$4.txt | sed 's/.*= //') $(tail -1 $out/call07_$1_$2_$3_t$4.txt | cut -c1-3000)"
}
run change serve-dots3-notes-closed48 6100000081 1
run parent serve-glm5-longctx-closed16 6100000082 1
run change serve-dots3-notes-closed48 2900000083 0
