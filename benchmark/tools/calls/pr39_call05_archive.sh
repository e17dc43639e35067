#!/bin/bash
# PR 39, chip call 5 (1 chip): the tree as git would commit it (build/archive_check =
# `git archive $(git write-tree)`), after the clean-up of the code: three untraced runs and one
# traced run of the new cell on seeds of their own, the fault table on one seed and the clean
# gap over eight more; from build/parent_overlay (the parent with this PR's BENCHMARK.json and
# benchmark/ laid over it) the new cell, which must fail at once, and one accepted cell traced,
# which must give its whole line; chip_smoke.py last.
out=/root/repo/chiprun_out/p39c5; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo/build/archive_check
for s in 3900000161 3900000162 314159265; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 0 \
    > $out/run.s$s.log 2> $out/run.s$s.err
  echo "seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/run.s$s.log | cut -c1-900)"
  grep -h "logits vs\|set-up\|program(s) built in the window" $out/run.s$s.log | cut -c1-330
done
s=3900000171; t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
  > $out/traced.s$s.log 2> $out/traced.s$s.err
echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-6000)"
grep -h "roofline\|logits vs\|set-up\|serve: window\|token gap" $out/traced.s$s.log | cut -c1-600
python3 benchmark/tools/calls/pr39_faults.py 3900000191 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -1 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py ONLY=clean $(seq 3900000201 3900000206) 271828182 4294967 \
  > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
cd /root/repo/build/parent_overlay
t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed 3900000260 --seconds 51 --trace 1 > $out/parent.log 2> $out/parent.err
echo "parent overlay on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -1 $out/parent.err | cut -c1-300
old=serve-mistral7b-longprompt-closed; t0=$(date +%s%N)
python3 benchmark/run.py --workload $old --seed 3900000261 --seconds 51 --trace 1 > $out/parent_old.log 2> $out/parent_old.err
echo "parent overlay on $old traced: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/parent_old.log | cut -c1-1500)"
cd /root/repo/build/archive_check
t0=$(date +%s)
python chip_smoke.py > $out/smoke.log 2> $out/smoke.err
echo "chip_smoke rc $? wall $(( $(date +%s) - t0 )) s"; tail -1 $out/smoke.log | cut -c1-600; tail -2 $out/smoke.err | cut -c1-400
