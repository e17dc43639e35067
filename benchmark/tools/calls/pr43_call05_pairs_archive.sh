#!/bin/bash
# PR 43, call 5 (1 chip): the other four accepted serving configurations, the parent beside the
# change (one pair each), then the committed files alone (build/archive_check = `git archive
# $(git write-tree)`): the new cell and one accepted cell with `--trace 1`.
PAIRS=1 bash benchmark/tools/calls/pr43_call03_pairs.sh p43c5 4300000500 serve-qwen3next-longchat-closed32 \
    serve-moonlight-longdoc-closed64 serve-lfm2-agent-closed128 serve-mistral7b-chat-steady
out=/root/repo/chiprun_out/p43c5
cd /root/repo/build/archive_check || exit 1
for cell in serve-ouro-reason-closed8 serve-mistral7b-longprompt-closed; do
    python3 benchmark/run.py --workload $cell --seed 4300000599 --seconds 51 --trace 1 \
        > $out/archive.$cell.log 2> $out/archive.$cell.err
    echo "archive $cell trace 1: rc $? $(tail -1 $out/archive.$cell.log | cut -c1-6000)"
done
