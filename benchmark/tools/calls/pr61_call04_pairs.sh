#!/bin/bash
# PR 61, calls 4 and 5: parent against change, three untraced pairs a cell,
# a seed a pair, sides alternating, on the cells that share the touched
# code.  CELLS="a b" chooses them (two cells a call fit its hour).
# build/parent is `git archive` of the parent commit with this PR's
# benchmark laid over it; the change is the working tree.
cd "$(dirname "$0")/../../.."
bash benchmark/tools/calls/pr61_overlay.sh
out=$PWD/chiprun_out/pr61; mkdir -p $out
run() { # side cell seed
  local dir=.; [ "$1" = parent ] && dir=build/parent
  (cd $dir && timeout -s KILL 900 python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace 0) > $out/pairs_$1_$2_$3.txt 2>&1
  echo "$1 $2 seed $3: $(grep 'logits vs' $out/pairs_$1_$2_$3.txt | sed 's/.*= //') $(tail -1 $out/pairs_$1_$2_$3.txt | cut -c1-600)"
}
n=0
for cell in $CELLS; do
  n=$((n + 1))
  run parent $cell 61000001${n}1; run change $cell 61000001${n}1
  run change $cell 61000001${n}2; run parent $cell 61000001${n}2
  run parent $cell 30000001${n}3; run change $cell 30000001${n}3
done
