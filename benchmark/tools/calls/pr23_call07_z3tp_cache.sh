#!/bin/bash
# PR 23, chip call 7 (4 chips), after the review: the ZeRO-3 x TP cell once cold and
# traced, once warm and untraced, with names and locations in the compile cache's key
# (runtime/engine.py now sets it): is the second run's set-up warm (the key is the same
# from run to run under shard_map and GSPMD too), and are all metrics still reported.
out=/root/repo/chiprun_out/p23c7; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
cd /root/repo/build/archive_check
for t in 1 0; do
    python3 benchmark/run.py --workload $cell --seed 2000000101 --seconds 51 --trace $t \
        > $out/change.$cell.s2000000101.t$t.log 2> $out/change.$cell.s2000000101.t$t.err
    echo "trace $t: rc $? $(tail -1 $out/change.$cell.s2000000101.t$t.log | cut -c1-2500)"
done
grep -h "by scope\|kernels matching\|steps in\|set-up\|no such scope" $out/*.log | cut -c1-1500
