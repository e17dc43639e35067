#!/bin/bash
# PR 35, chip call 5 (1 chip): one pair parent / change of each accepted one-chip cell (parent,
# change on one seed), from build/parent (git archive of the parent commit) and the working
# tree; the cells that share the most changed code first.
out=/root/repo/chiprun_out/p35c5; mkdir -p $out
seed=3500000200
for c in serve-qwen3next-longchat-closed32 serve-moonlight-longdoc-closed64 serve-olmoe-chat-closed32 \
         serve-mistral7b-longprompt-closed serve-mistral7b-chat-steady train-gpt2large-d64-s1k; do
  seed=$((seed+1))
  for side in build/parent .; do
    ( cd /root/repo/$side; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $c --seed $seed --seconds 51 --trace 0 \
        > $out/$c.$(basename $side).log 2> $out/$c.$(basename $side).err
      echo "$c $side seed $seed: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/$c.$(basename $side).log | cut -c1-700)" )
  done
done
