#!/bin/bash
# PR 31, chip call 7 (1 chip): the committed tree once more after BENCHMARK.json's last
# edit (the cell no longer lists tpot_p50_ms): one untraced and one traced run of the new
# cell from build/archive_check, and the parent's traced run of an accepted cell with this
# PR's benchmark files laid over it (build/parent_overlay): its new readers find nothing
# there and must not raise.
out=/root/repo/chiprun_out/p31c7; mkdir -p $out
c=serve-moonlight-longdoc-closed64
cd /root/repo/build/archive_check
for tr in 0 1; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed 310000020$tr --seconds 51 --trace $tr \
    > $out/run.t$tr.log 2> $out/run.t$tr.err
  echo "trace $tr: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/run.t$tr.log | cut -c1-2600)"
  grep -h "logits vs\|set-up" $out/run.t$tr.log | cut -c1-200; tail -2 $out/run.t$tr.err | cut -c1-300
done
cd /root/repo/build/parent_overlay
t0=$(date +%s%N)
python3 benchmark/run.py --workload serve-olmoe-chat-closed32 --seed 3100000210 --seconds 51 --trace 1 \
  > $out/parent_olmoe.log 2> $out/parent_olmoe.err
echo "parent overlay, olmoe traced: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/parent_olmoe.log | cut -c1-1500)"
