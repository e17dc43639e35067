#!/bin/bash
# PR 35, chip call 6 (1 chip): the tree as git would commit it (build/archive_check =
# `git archive $(git write-tree)`), after the clean-up of the code: chip_smoke.py, three
# untraced runs and one traced run of the new cell on seeds of their own; at the final seeding
# (embedding 0.7) the faults on two seeds, the clean gap over 20 seeds of its own, and the
# four-request interleaved check.
out=/root/repo/chiprun_out/p35c6; mkdir -p $out
c=serve-lfm2-agent-closed128
cd /root/repo/build/archive_check
t0=$(date +%s)
python chip_smoke.py > $out/smoke.log 2> $out/smoke.err
echo "chip_smoke rc $? wall $(( $(date +%s) - t0 )) s"; tail -1 $out/smoke.log | cut -c1-600
grep "chip_smoke: conv" $out/smoke.log | cut -c1-1200; tail -2 $out/smoke.err | cut -c1-400
cp chiprun_out/chip_smoke.json $out/ 2>/dev/null
for s in 3500000161 3500000162 314159265; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 0 \
    > $out/run.s$s.log 2> $out/run.s$s.err
  echo "seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/run.s$s.log | cut -c1-900)"
  grep -h "logits vs\|set-up\|program(s) built in the window" $out/run.s$s.log | cut -c1-300
done
s=3500000171; t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
  > $out/traced.s$s.log 2> $out/traced.s$s.err
echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-5000)"
python3 benchmark/tools/calls/pr35_faults.py 3500000191 3500000192 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -1 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr35_faults.py ONLY=clean $(seq 3500000201 3500000214) \
  1442695040 2718281828 161803398 1123581321 662607015 299792458 > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
python3 benchmark/tools/calls/pr35_interleaved.py 3500000193 > $out/inter.log 2> $out/inter.err
echo "interleaved rc $?"; grep "^seed\|^interleaved" $out/inter.log; tail -1 $out/inter.err | cut -c1-300
