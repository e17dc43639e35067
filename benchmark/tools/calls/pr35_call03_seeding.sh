#!/bin/bash
# PR 35, chip call 3 (1 chip): call 2 read the dropped-bias fault at 0.0058, under the limit:
# the routed experts at a quarter of the residual scale barely reach the logits, and a tied
# head's own-token logit (|e|^2 / rms) sets the largest |logit| the gap is divided by.  The
# clean check and the dropped bias (and once the zeroed tail) at four seedings, two seeds each.
out=/root/repo/chiprun_out/p35c3; mkdir -p $out
cd /root/repo
for set in "EXPERT_DOWN=1 EMBED_STD=1" "EXPERT_DOWN=1 EMBED_STD=0.5" "EXPERT_DOWN=1 EMBED_STD=0.25" "EXPERT_DOWN=2 EMBED_STD=0.5"; do
  tag=$(echo $set | tr ' =' '__')
  python3 benchmark/tools/calls/pr35_faults.py $set ONLY=clean,bias_dropped,tail_zeroed 3500000121 3500000122 \
    > $out/seeding.$tag.log 2> $out/seeding.$tag.err
  echo "$set: rc $?"; grep "^seed\|^clean\|^seeding" $out/seeding.$tag.log | cut -c1-200; tail -1 $out/seeding.$tag.err | cut -c1-300
done
