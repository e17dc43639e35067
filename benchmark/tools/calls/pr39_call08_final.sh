#!/bin/bash
# PR 39, chip call 8 (1 chip), after the review: the tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`) at EXPERT_DOWN 0.075 and the window pool
# of 1,095 blocks.  The set of six that the baseline is stated from (measure.py, seeds of their
# own); one traced run with a clock on every log line (the set-up by part); the whole fault table
# and the control on one seed, the routed experts dropped over twelve seeds and weights from
# score + bias over six (call 7 was lost before it held a machine); from build/parent_overlay
# (the parent with this PR's BENCHMARK.json and benchmark/ laid over it) the new cell, which must
# fail at once, and one accepted cell traced, which must give its whole line.
out=/root/repo/chiprun_out/p39c8; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo/build/archive_check
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p39c8m --sets 1 --runs 6 --seed0 3900000401 \
    --trace 0 $c > $out/measure6.log 2> $out/measure6.err
echo "measure (6) rc $? wall $(( $(date +%s) - t0 )) s"; tail -40 $out/measure6.log | cut -c1-1800
cp -r chiprun_out/p39c8m /root/repo/chiprun_out/ 2>/dev/null
grep -h "logits vs\|set-up\|shape ladder\|program(s) built in the window\|token gap" chiprun_out/p39c8m/*.log | cut -c1-330
s=3900000411; t0=$(date +%s%N)
python3 -u benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 2> $out/traced.s$s.err \
  | while IFS= read -r l; do printf '%s %s\n' "$(date +%s.%N | cut -c1-14)" "$l"; done > $out/traced.s$s.log
echo "traced seed $s: rc ${PIPESTATUS[0]} wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-6000)"
grep -h "^[0-9.]* # \|^[0-9.]* \[" $out/traced.s$s.log | grep -h "InferenceEngineV2\|serve: weights\|logits vs\|shape ladder\|pre-roll\|serve: window\|set-up\|roofline\|token gap\|seed $s" | cut -c1-400
python3 benchmark/tools/calls/pr39_faults.py 3900000421 > $out/faults.log 2> $out/faults.err
echo "faults rc $?"; grep "^seed\|^clean\|^seeding" $out/faults.log; tail -1 $out/faults.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py ONLY=routed_dropped $(seq 3900000331 3900000342) > $out/dropped.log 2> $out/dropped.err
echo "dropped rc $?"; grep "^seed" $out/dropped.log | cut -c1-200; tail -1 $out/dropped.err | cut -c1-300
python3 benchmark/tools/calls/pr39_faults.py ONLY=bias_in_weights $(seq 3900000343 3900000348) > $out/biasw.log 2> $out/biasw.err
echo "bias_in_weights rc $?"; grep "^seed" $out/biasw.log | cut -c1-200; tail -1 $out/biasw.err | cut -c1-300
cd /root/repo/build/parent_overlay
t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed 3900000460 --seconds 51 --trace 1 > $out/parent.log 2> $out/parent.err
echo "parent overlay on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -1 $out/parent.err | cut -c1-300
old=serve-olmoe-chat-closed32; t0=$(date +%s%N)
python3 benchmark/run.py --workload $old --seed 3900000461 --seconds 51 --trace 1 > $out/parent_old.log 2> $out/parent_old.err
echo "parent overlay on $old traced: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/parent_old.log | cut -c1-2500)"
