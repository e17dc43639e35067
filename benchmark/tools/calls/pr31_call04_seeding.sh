#!/bin/bash
# PR 31, chip call 4 (1 chip): the seeding constants of benchmark/families/moonlight.py.
# Call 3 read bias_in_weights 0.0164-0.0167 and own_chunk_only 0.0277 / 0.0317 against
# 0.03 (neither reliably seen) at BIAS_MEAN -0.5, Q_SCALE 1.  Candidates, two seeds each,
# the clean program and those two faults.
out=/root/repo/chiprun_out/p31c4; mkdir -p $out
cd /root/repo
i=0
for cand in "BIAS_MEAN=-0.8 Q_SCALE=2.0" "BIAS_MEAN=-0.7 Q_SCALE=2.0" "BIAS_MEAN=-0.8 Q_SCALE=1.5 EXPERT_DOWN=0.2" "BIAS_MEAN=-0.9 Q_SCALE=3.0"; do
  i=$((i+1))
  python3 benchmark/tools/calls/pr31_faults.py $cand ONLY=clean,bias_in_weights,own_chunk_only \
      3100000031 3100000032 > $out/cand$i.log 2> $out/cand$i.err
  echo "candidate $i ($cand): rc $?"; grep "^seed\|^seeding" $out/cand$i.log; tail -2 $out/cand$i.err | cut -c1-300
done
