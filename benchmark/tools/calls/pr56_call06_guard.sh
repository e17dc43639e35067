#!/bin/bash
# PR 56, call 6: the Qwen3-Next guard once more on the final tree (the
# committed files alone, build/archive_check) beside the parent
# (build/parent, its own benchmark files): call 5's single run read 3% under
# call 3's four.  Parent, change, change, parent, a seed a pair.
cd "$(dirname "$0")/../../../"
out=$PWD/chiprun_out/pr56; mkdir -p $out
Q=serve-qwen3next-longchat-closed32
run() { # side dir seed
  (cd $2 && python3 benchmark/run.py --workload $Q --seed $3 --seconds 51 --trace 0) > $out/call06_q_$1_$3.txt 2>&1
  echo "$1 seed $3: $(tail -1 $out/call06_q_$1_$3.txt | cut -c1-420)"
}
run parent build/parent 5600000041; run change build/archive_check 5600000041
run change build/archive_check 2147484041; run parent build/parent 2147484041
