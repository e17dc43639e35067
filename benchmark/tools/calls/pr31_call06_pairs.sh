#!/bin/bash
# PR 31, chip call 6 (1 chip): one pair parent / change of each accepted one-chip cell
# (parent, change on one seed), from build/parent (git archive of the parent commit) and
# build/archive_check (this PR's tree as git would commit it).
out=/root/repo/chiprun_out/p31c6; mkdir -p $out
seed=3100000100
for c in serve-mistral7b-chat-steady serve-mistral7b-longprompt-closed serve-olmoe-chat-closed32 \
         serve-qwen3next-longchat-closed32 train-gpt2large-d64-s1k; do
  seed=$((seed+1))
  for side in parent archive_check; do
    ( cd /root/repo/build/$side
      python3 benchmark/run.py --workload $c --seed $seed --seconds 51 --trace 0 \
        > $out/$c.$side.log 2> $out/$c.$side.err
      echo "$c $side seed $seed: rc $? $(tail -1 $out/$c.$side.log | cut -c1-700)" )
  done
done
