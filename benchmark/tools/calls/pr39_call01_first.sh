#!/bin/bash
# PR 39, chip call 1 (1 chip): the new cell for the first time.  The parent with this PR's
# benchmark laid over it (build/parent_overlay) on the new cell, which must fail at once; one
# untraced and one traced run on seeds of their own; the fault table on one seed.
out=/root/repo/chiprun_out/p39c1; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo
( cd build/parent_overlay; t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed 3900000060 --seconds 51 --trace 0 \
    > $out/parent.log 2> $out/parent.err
  echo "parent on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -2 $out/parent.err | cut -c1-400 )
for s in 3900000061; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 0 \
    > $out/run.s$s.log 2> $out/run.s$s.err
  echo "seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/run.s$s.log | cut -c1-900)"
  grep -h "logits vs\|set-up\|program(s) built in the window\|serve: \|shape ladder" $out/run.s$s.log $out/run.s$s.err | cut -c1-400
  tail -3 $out/run.s$s.err | cut -c1-300
done
s=3900000081; t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
  > $out/traced.s$s.log 2> $out/traced.s$s.err
echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-6000)"
grep -h "by scope\|roofline\|host ms per tick\|logits vs\|set-up\|matching\|launches\|longest gap\|starved\|serve: " $out/traced.s$s.log $out/traced.s$s.err | cut -c1-1800
tail -3 $out/traced.s$s.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py 3900000091 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean" $out/faults.log; tail -2 $out/faults.err | cut -c1-300
