#!/bin/bash
# PR 39, chip call 3 (1 chip): the new cell at the seeding call 2 placed.  Three untraced runs,
# then a set of six (measure.py: medians and spreads), every run on a seed of its own; two
# traced runs.
out=/root/repo/chiprun_out/p39c3; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p39c3a --sets 1 --runs 3 --seed0 3900000121 \
    --trace 0 $c > $out/measure3.log 2> $out/measure3.err
echo "measure (3) rc $? wall $(( $(date +%s) - t0 )) s"; tail -30 $out/measure3.log | cut -c1-1800
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p39c3b --sets 1 --runs 6 --seed0 3900000141 \
    --trace 0 $c > $out/measure6.log 2> $out/measure6.err
echo "measure (6) rc $? wall $(( $(date +%s) - t0 )) s"; tail -40 $out/measure6.log | cut -c1-1800
grep -h "logits vs\|set-up\|program(s) built in the window\|token gap" chiprun_out/p39c3a/*.log chiprun_out/p39c3b/*.log | cut -c1-330
for s in 3900000181; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
    > $out/traced.s$s.log 2> $out/traced.s$s.err
  echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-6000)"
  grep -h "by scope\|roofline\|host ms per tick\|logits vs\|set-up\|matching\|launches\|longest gap\|starved\|serve: window\|token gap" $out/traced.s$s.log | cut -c1-1800
done
