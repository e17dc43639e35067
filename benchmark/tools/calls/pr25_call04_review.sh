#!/bin/bash
# PR 25, chip call 4 (1 chip), after the review: the second readings beside each limit
# (pr25_review_readings.py: moe/combine both ways, GMM_TOL, the router alone, the depth-2
# engine with a fault); the runner's logits check with every routing recorded, the same
# two seeds with the router GEMM at Precision.HIGHEST (the program) and at the TPU's
# default precision (ROUTER=default); and one untraced run of the cell with its pre-roll
# back at ISSUE 25's 10 s, every line of its log stamped with the wall clock, to see
# where the set-up and the wall time go.
out=/root/repo/chiprun_out/p25c4; mkdir -p $out
cd /root/repo
python3 benchmark/tools/calls/pr25_review_readings.py > $out/readings.log 2> $out/readings.err
echo "readings rc $?"; grep -v "^\[" $out/readings.log | cut -c1-400; tail -3 $out/readings.err | cut -c1-600
python3 benchmark/tools/calls/pr25_routing_agreement.py 2500000071 2500000072 \
    > $out/routing_highest.log 2> $out/routing_highest.err
echo "routing (highest) rc $?"; grep "^seed" $out/routing_highest.log | cut -c1-500
ROUTER=default python3 benchmark/tools/calls/pr25_routing_agreement.py 2500000071 2500000072 \
    > $out/routing_default.log 2> $out/routing_default.err
echo "routing (default) rc $?"; grep "^seed\|^router" $out/routing_default.log | cut -c1-500
tail -3 $out/routing_default.err | cut -c1-600
c=serve-olmoe-chat-closed32; s=2500000073
t0=$(date +%s.%N)
python3 -u benchmark/run.py --workload $c --seed $s --seconds 51 --trace 0 2> $out/change.s$s.t0.err \
    | while IFS= read -r line; do echo "$(date +%s.%N) $line"; done > $out/change.s$s.t0.log
t1=$(date +%s.%N)
echo "start $t0 end $t1"; cut -c1-900 $out/change.s$s.t0.log
