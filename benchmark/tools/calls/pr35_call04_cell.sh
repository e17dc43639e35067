#!/bin/bash
# PR 35, chip call 4 (1 chip): the new cell at the seeding call 3 placed (embedding 0.6, no
# further scale on the routed experts).  The four seeded faults on two seeds; the clean
# check's gap over 20 seeds (mean + 4 standard deviations against 0.03); one set of six
# untraced runs on seeds of their own (measure.py: medians and spreads); two traced runs.
out=/root/repo/chiprun_out/p35c4; mkdir -p $out
c=serve-lfm2-agent-closed128
cd /root/repo
python3 benchmark/tools/calls/pr35_faults.py 3500000131 3500000132 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -1 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr35_faults.py ONLY=clean $(seq 3500000101 3500000112) \
  1618033988 2147483999 1203555967 977312645 271828182 314159265 4294967 2000000011 \
  > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p35c4m --sets 1 --runs 6 --seed0 3500000141 \
    --trace 0 $c > $out/measure.log 2> $out/measure.err
echo "measure rc $? wall $(( $(date +%s) - t0 )) s"; tail -40 $out/measure.log | cut -c1-1800
grep -h "logits vs\|set-up\|program(s) built in the window" chiprun_out/p35c4m/*.log | cut -c1-260
for s in 3500000181 1203555967; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
    > $out/traced.s$s.log 2> $out/traced.s$s.err
  echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-5000)"
  grep -h "by scope\|roofline\|host ms per tick\|logits vs\|set-up\|matching\|launches\|longest gap\|starved" $out/traced.s$s.log | cut -c1-1800
done
