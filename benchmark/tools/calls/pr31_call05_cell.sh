#!/bin/bash
# PR 31, chip call 5 (1 chip): the new cell from the tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`), after the clean-up of the code.
# chip_smoke.py; the parent with this PR's benchmark laid over it (build/parent_overlay) on
# the new cell, which must fail at once; two sets of six untraced runs on new seeds
# (measure.py: medians and spreads); two traced runs; the three seeded faults; the
# four-request interleaved check on two seeds.
out=/root/repo/chiprun_out/p31c5; mkdir -p $out
c=serve-moonlight-longdoc-closed64
cd /root/repo/build/archive_check
t0=$(date +%s)
python chip_smoke.py > $out/smoke.log 2> $out/smoke.err
echo "chip_smoke rc $? wall $(( $(date +%s) - t0 )) s"; tail -1 $out/smoke.log | cut -c1-600
grep "chip_smoke: mla\|chip_smoke: kernels" $out/smoke.log | cut -c1-2500; tail -2 $out/smoke.err | cut -c1-400
cp chiprun_out/chip_smoke.json $out/ 2>/dev/null
( cd /root/repo/build/parent_overlay; t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed 3100000060 --seconds 51 --trace 0 \
    > $out/parent.log 2> $out/parent.err
  echo "parent on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -2 $out/parent.err | cut -c1-400 )
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p31c5m --sets 2 --runs 6 --seed0 3100000061 \
    --trace 0 $c > $out/measure.log 2> $out/measure.err
echo "measure rc $? wall $(( $(date +%s) - t0 )) s"; tail -60 $out/measure.log | cut -c1-1800
mkdir -p /root/repo/chiprun_out/p31c5m; cp -r chiprun_out/p31c5m/. /root/repo/chiprun_out/p31c5m/ 2>/dev/null
grep -h "logits vs\|set-up\|program(s) built in the window" chiprun_out/p31c5m/*.log | cut -c1-260
for s in 3100000081 3100000082; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
    > $out/traced.s$s.log 2> $out/traced.s$s.err
  echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-4500)"
  grep -h "by scope\|roofline\|host ms per tick\|logits vs\|set-up\|matching" $out/traced.s$s.log | cut -c1-1600
done
python3 benchmark/tools/calls/pr31_faults.py 3100000091 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed" $out/faults.log; tail -2 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr31_interleaved.py 3100000093 3100000094 > $out/inter.log 2> $out/inter.err
echo "interleaved rc $?"; grep "^seed\|^interleaved" $out/inter.log; tail -2 $out/inter.err | cut -c1-300
