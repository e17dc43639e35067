#!/bin/bash
# PR 31, chip call 3 (1 chip): the probe (both latent self-test cases; the check's rows and
# the four-request interleaved check on a float32 engine at depth 3 and on the cell's own
# bf16 engine), then the three seeded faults on two seeds, with the seeding of
# benchmark/families/moonlight.py as it now stands (routed down projections at 1/4, the
# selection bias at -0.5 + 0.1 z).
out=/root/repo/chiprun_out/p31c3; mkdir -p $out
cd /root/repo
python3 benchmark/tools/calls/pr31_probe.py 3100000021 3100000022 > $out/probe.log 2> $out/probe.err
echo "probe rc $?"; grep "^latent\|^seed" $out/probe.log | cut -c1-1500; tail -3 $out/probe.err | cut -c1-600
python3 benchmark/tools/calls/pr31_faults.py 3100000011 3100000012 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed" $out/faults.log; tail -3 $out/faults.err | cut -c1-500
