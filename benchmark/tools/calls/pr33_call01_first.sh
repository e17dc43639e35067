#!/bin/bash
# PR 33, chip call 1 (1 chip): first contact of the launch record with the chip, on the working
# tree.  One traced run of each serving cell through pr33_probe.py, which prints the result line
# and leaves what the readers were developed against in chiprun_out/p33c1/<cell>.json.gz.
out=/root/repo/chiprun_out/p33c1; mkdir -p $out
s=3300000011
for c in serve-mistral7b-chat-steady serve-moonlight-longdoc-closed64 serve-olmoe-chat-closed32 \
         serve-qwen3next-longchat-closed32 serve-mistral7b-longprompt-closed; do
  t0=$(date +%s)
  python3 benchmark/tools/calls/pr33_probe.py $c $s > $out/$c.log 2> $out/$c.err
  echo "$c seed $s: rc $? wall $(( $(date +%s) - t0 )) s $(tail -1 $out/$c.log | cut -c1-3000)"
  grep -h "launches\|made .* launches\|token gap p50\|set-up\|logits vs" $out/$c.log | cut -c1-1800
  tail -3 $out/$c.err | cut -c1-600
  s=$((s + 1))
done
ls -la $out
