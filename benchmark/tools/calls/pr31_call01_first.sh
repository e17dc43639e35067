#!/bin/bash
# PR 31, chip call 1 (1 chip): chip_smoke.py first (the new mla phase and the two latent
# self-test cases among its gates), then the new cell once untraced and once traced, then
# the three seeded faults on one seed.
out=/root/repo/chiprun_out/p31c1; mkdir -p $out
cd /root/repo
t0=$(date +%s)
python chip_smoke.py > $out/smoke.log 2> $out/smoke.err
echo "chip_smoke rc $? wall $(( $(date +%s) - t0 )) s"; tail -3 $out/smoke.log | cut -c1-3000
cp chiprun_out/chip_smoke.json $out/ 2>/dev/null
c=serve-moonlight-longdoc-closed64
for tr in 0 1; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed 310000000$tr --seconds 51 --trace $tr \
    > $out/run.t$tr.log 2> $out/run.t$tr.err
  echo "trace $tr: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"
  grep -h "^# " $out/run.t$tr.log | cut -c1-1500 | tail -40
  tail -1 $out/run.t$tr.log | cut -c1-6000
  tail -5 $out/run.t$tr.err | cut -c1-600
done
python3 benchmark/tools/calls/pr31_faults.py 3100000011 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed" $out/faults.log; tail -3 $out/faults.err | cut -c1-500
