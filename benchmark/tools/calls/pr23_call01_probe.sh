#!/bin/bash
# PR 23, chip call 1 (1 chip): one traced run of the chat cell and of the GPT-2 cell
# with the new spans, counters, kernel names and scopes; the statistics of the first
# ten events of each line of both traces (which one carries the op_name?); both
# xplane files come back so that the scope reader can be finished off the chip.
python3 benchmark/tools/measure.py --tag p23c1 --sets 1 --runs 1 --seed0 7 --trace 1 \
    serve-mistral7b-chat-steady train-gpt2large-d64-s1k
for cell in serve-mistral7b-chat-steady train-gpt2large-d64-s1k; do
    x=$(ls bench_out/$cell/trace/plugins/profile/*/*.xplane.pb | tail -1)
    python3 benchmark/tools/trace_dump.py "$x" 10 > chiprun_out/p23c1/$cell.dump.txt 2>&1
    cp "$x" chiprun_out/p23c1/$cell.xplane.pb
done
grep -h "^# serve: token gap\|host ms per tick\|by scope\|kernels matching\|^# train: attention\|^{" \
    chiprun_out/p23c1/*.log | cut -c1-6000
grep -A12 "XLA Ops" chiprun_out/p23c1/serve-mistral7b-chat-steady.dump.txt | cut -c1-1500 | head -40
