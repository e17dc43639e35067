#!/bin/bash
# PR 39, chip call 6 (1 chip), after the review of the first submission: the routed experts'
# scale once more, now placed so that the check SEES them (EXPERT_DOWN 0.075, the family's value
# since; PERF.md section 6 has the prediction written before this call).  The three faults of
# the routed experts and the low-precision control (the float32 reference on weights cut to
# float8_e4m3's 3 mantissa bits, through the runner's own comparison) on two seeds, then the
# clean gap over twenty-four seeds of their own.
out=/root/repo/chiprun_out/p39c6; mkdir -p $out
cd /root/repo
only=ONLY=clean,routed_dropped,bias_in_weights,bias_dropped,reference_low_precision
python3 benchmark/tools/calls/pr39_faults.py $only 3900000301 3900000302 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log | cut -c1-200; tail -2 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py ONLY=clean $(seq 3900000311 3900000328) 1442695041 2718281829 \
    161803399 1123581322 662607016 299792459 > $out/gaps.log 2> $out/gaps.err
echo "gaps rc $?"; grep "^seed\|^clean\|^seeding" $out/gaps.log | cut -c1-200; tail -1 $out/gaps.err | cut -c1-300
