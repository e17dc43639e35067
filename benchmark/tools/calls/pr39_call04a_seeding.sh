#!/bin/bash
# PR 39, chip call 4, first part (1 chip; pr39_call04_pairs.sh follows in the same machine): the
# routed experts' scale once more.  Calls 2 + 3 read the clean check at EXPERT_DOWN 0.1 up to
# 0.0235 of the 0.03 allowed (26 readings, a routing near-tie a bf16 rounding decides the other
# way in nine of sixteen): too near for a check every later PR runs on fresh seeds.  Here: the
# clean gap over twenty seeds and the fault table at the family's value (0.05), then the clean
# gap over eight of the seeds at 0.07.
out=/root/repo/chiprun_out/p39c4a; mkdir -p $out
cd /root/repo
seeds="3900000091 3900000101 3900000102 3900000103 3900000104 3900000105 3900000106 3900000107 1442695040 2718281828 161803398 1123581321"
python3 benchmark/tools/calls/pr39_faults.py ONLY=clean $seeds 662607015 299792458 3900000108 3900000109 \
    3900000110 3900000111 3900000112 3900000113 > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean\|^seeding" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py 3900000091 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -2 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py EXPERT_DOWN=0.07 ONLY=clean $(echo $seeds | cut -d' ' -f1-8) > $out/gaps_07.log 2> $out/gaps_07.err
echo "gaps at 0.07 rc $?"; grep "^seed\|^clean\|^seeding" $out/gaps_07.log | cut -c1-200; tail -1 $out/gaps_07.err | cut -c1-300
