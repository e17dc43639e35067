#!/bin/bash
# PR 24, chip call 6 (1 chip): is the decode tick's slow mode the vCPU the process sits on
# (call 4: slow runs on CPUs 4, 6, 12, 12; fast on 0, 0, 1, 1, 5, 8, 10, 10)?  The long-prompt
# cell, 8 s windows, the whole process held to two CPUs by taskset: {0,1}, {4,6}, {8,10}, {11,12},
# then {0,1} and {4,6} once more in the other order.
out=/root/repo/chiprun_out/p24c6; mkdir -p $out
l=serve-mistral7b-longprompt-closed; i=0
for cpus in 0,1 4,6 8,10 11,12 4,6 0,1; do
  i=$((i + 1))
  ( cd /root/repo/build/archive_check && taskset -c $cpus python3 /root/repo/build/diag_modes.py \
      --workload $l --seed 240000009$i --seconds 8 --trace 0 > $out/pin$i.log 2> $out/pin$i.err
    echo "cpus $cpus: rc $? $(grep -h 'token gap' $out/pin$i.log | cut -c1-90) $(tail -1 $out/pin$i.log | cut -c1-200)"
    grep -h '# diag after' $out/pin$i.log | cut -c1-120 )
done
