#!/bin/bash
# PR 29, chip call 3 (1 chip): the new cell from the tree as git would commit it
# (build/archive_check = `git archive $(git write-tree)`), after the clean-up of the code.
# The parent with this PR's benchmark laid over it (build/parent_overlay) on the new
# cell, which must fail at once; six untraced runs on new seeds (measure.py: one set of
# six, medians and spreads); two traced runs; the four-request interleaved check.
out=/root/repo/chiprun_out/p29c3; mkdir -p $out
c=serve-qwen3next-longchat-closed32
( cd /root/repo/build/parent_overlay; t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed 2900000060 --seconds 51 --trace 0 \
    > $out/parent.log 2> $out/parent.err
  echo "parent on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -2 $out/parent.err | cut -c1-400 )
cd /root/repo/build/archive_check
python3 benchmark/tools/measure.py --tag p29c3m --sets 1 --runs 6 --seed0 2900000061 \
    --trace 0 $c > $out/measure.log 2> $out/measure.err
echo "measure rc $?"; tail -40 $out/measure.log | cut -c1-1800
mkdir -p /root/repo/chiprun_out/p29c3m; cp -r chiprun_out/p29c3m/. /root/repo/chiprun_out/p29c3m/ 2>/dev/null
grep -h "logits vs\|set-up\|program(s) built in the window" chiprun_out/p29c3m/*.log | cut -c1-260
for s in 2900000071 2900000072; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 \
    > $out/traced.s$s.log 2> $out/traced.s$s.err
  echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-3800)"
  grep -h "by scope\|roofline\|host ms per tick\|logits vs" $out/traced.s$s.log | cut -c1-1400
done
python3 benchmark/tools/calls/pr29_interleaved.py 2900000081 2900000082 > $out/inter.log 2> $out/inter.err
echo "interleaved rc $?"; grep "^seed\|^interleaved" $out/inter.log
