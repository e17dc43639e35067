#!/bin/bash
# PR 25, chip call 3 (1 chip): the tree as git would commit it (build/archive_check =
# `git archive $(git write-tree)`), after the clean-up of the code.  chip_smoke.py whole
# (its new moe phase included); the new cell: three untraced runs on new seeds and one
# traced; the parent with this PR's benchmark laid over it (build/parent_overlay): the new
# cell, which must fail at once, and one accepted cell traced, which must still run with
# the added readers and files; the same accepted cell traced from the archive.
out=/root/repo/chiprun_out/p25c3; mkdir -p $out
( cd /root/repo/build/archive_check && python3 chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err
  echo "chip_smoke (archive) rc $? $(tail -1 $out/chip_smoke.log | cut -c1-300)"
  grep "moe ok\|compile seconds" $out/chip_smoke.log | cut -c1-2500
  cp chiprun_out/chip_smoke.json $out/ 2>/dev/null )
o=serve-olmoe-chat-closed32; l=serve-mistral7b-longprompt-closed
run() {  # side cell seed trace
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent_overlay
    ( cd $dir; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err
      rc=$?; t1=$(date +%s%N)
      echo "$1 $2 seed $3 trace $4: rc $rc wall $(( (t1 - t0) / 1000000 )) ms $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-2600)"
      [ $rc != 0 ] && tail -4 $out/$1.$2.s$3.t$4.err | cut -c1-600 )
}
for s in 2500000061 2500000062 2500000063; do run change $o $s 0; done
run change $o 2500000064 1
run parent $o 2500000061 0
run parent $l 2500000065 1
run change $l 2500000065 1
grep -h "logits vs\|set-up" $out/change.$o.*.log | cut -c1-300
grep -h "by scope\|gmm roofline\|host ms per tick" $out/change.$o.s2500000064.t1.log | cut -c1-1200
