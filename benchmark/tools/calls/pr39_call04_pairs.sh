#!/bin/bash
# PR 39, chip call 4, second part (1 chip, after pr39_call04a_seeding.sh in the same machine): one
# pair parent / change of each accepted serving cell (parent, change on one seed), from
# build/parent (git archive of the parent commit) and the working tree; the cells that share the
# most changed code first (the tiled prefill read, the ragged engine and its state manager, the
# scheduler).  The two training cells run none of the changed code and are left to the driver.
out=/root/repo/chiprun_out/p39c4; mkdir -p $out
seed=3900000200
for c in serve-mistral7b-longprompt-closed serve-moonlight-longdoc-closed64 serve-lfm2-agent-closed128 \
         serve-qwen3next-longchat-closed32 serve-olmoe-chat-closed32 serve-mistral7b-chat-steady; do
  seed=$((seed+1))
  for side in build/parent .; do
    ( cd /root/repo/$side; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $c --seed $seed --seconds 51 --trace 0 \
        > $out/$c.$(basename $side).log 2> $out/$c.$(basename $side).err
      echo "$c $side seed $seed: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/$c.$(basename $side).log | cut -c1-700)" )
  done
done
