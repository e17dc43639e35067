#!/bin/bash
# PR 39, chip call 2 (1 chip; run in one machine with call 3, after it): how the seeded scales
# were chosen.  The clean check over sixteen seeds at the family's EXPERT_DOWN, the fault table
# on one seed at the family's values, then twelve of the seeds with the routed experts at scale
# 1 (how often a routing near-tie falls among the checked rows, and what it reads).
out=/root/repo/chiprun_out/p39c2; mkdir -p $out
cd /root/repo
seeds="3900000091 3900000101 3900000102 3900000103 3900000104 3900000105 3900000106 3900000107 1442695040 2718281828 161803398 1123581321"
python3 benchmark/tools/calls/pr39_faults.py ONLY=clean $seeds 662607015 299792458 3900000108 3900000109 > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean\|^seeding" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py 3900000091 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -2 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py EXPERT_DOWN=1.0 ONLY=clean $seeds > $out/gaps_r1.log 2> $out/gaps_r1.err
echo "gaps at 1.0 rc $?"; grep "^seed\|^clean\|^seeding" $out/gaps_r1.log | cut -c1-200; tail -1 $out/gaps_r1.err | cut -c1-300
