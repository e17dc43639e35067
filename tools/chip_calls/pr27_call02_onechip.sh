#!/bin/bash
# PR 27, chip call 2 (1 chip): chip_smoke.py on one chip from the tree git would commit
# (build/archive_check = `git archive $(git write-tree)`), then one cell of each one-chip
# configuration whose programs run a file this PR edited (ragged_llama.py: both serving
# configurations; runtime/engine.py: GPT-2-Large), that tree against build/parent =
# `git archive 74eee09`: parent, change, change, parent with tracing off, a seed per pair.
out=/root/repo/chiprun_out/p27c2; mkdir -p $out
( cd /root/repo/build/archive_check && python3 chip_smoke.py > $out/smoke1.log 2> $out/smoke1.err )
echo "chip_smoke on one chip: rc $? $(tail -1 $out/smoke1.log | cut -c1-600)"
run() {  # cell side seed
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace 0 \
        > $out/$1.$2.s$3.log 2> $out/$1.$2.s$3.err )
    echo "$1 $2 seed $3: rc $? $(tail -1 $out/$1.$2.s$3.log | cut -c1-2500)"
}
for cell in serve-mistral7b-chat-steady train-gpt2large-d64-s1k serve-olmoe-chat-closed32; do
    run $cell parent 2700000021; run $cell archive_check 2700000021
    run $cell archive_check 2700000022; run $cell parent 2700000022
done
