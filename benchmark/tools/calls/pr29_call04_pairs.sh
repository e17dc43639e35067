#!/bin/bash
# PR 29, chip call 4 (1 chip): parent against change on each accepted one-chip cell, the
# two sides of a comparison on one seed: the OLMoE cell (the cell that shares the expert
# layer this PR changed) in the order parent, change, change, parent, the others one
# pair each; both from unpacked archives: build/parent_overlay (`git
# archive 8c72620` with this PR's BENCHMARK.json and benchmark/ laid over it, as the
# driver does for traced runs; an accepted cell's untraced run reads nothing this PR
# added) and build/archive_check (`git archive $(git write-tree)`).
out=/root/repo/chiprun_out/p29c4; mkdir -p $out
run() {  # side cell seed tag
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent_overlay
    ( cd $dir; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace 0 \
        > $out/$1.$2.$4.log 2> $out/$1.$2.$4.err
      rc=$?; t1=$(date +%s%N)
      echo "$1 $2 seed $3 ($4): rc $rc wall $(( (t1 - t0) / 1000000 )) ms $(tail -1 $out/$1.$2.$4.log | cut -c1-700)"
      [ $rc != 0 ] && tail -4 $out/$1.$2.$4.err | cut -c1-600 )
}
cell=serve-olmoe-chat-closed32
run parent $cell 2900000091 a; run change $cell 2900000091 a
run change $cell 2900000092 b; run parent $cell 2900000092 b
s=2900000092
for cell in serve-mistral7b-longprompt-closed serve-mistral7b-chat-steady \
            train-gpt2large-d64-s1k; do
  s=$((s + 1))
  run parent $cell $s a; run change $cell $s a
done
