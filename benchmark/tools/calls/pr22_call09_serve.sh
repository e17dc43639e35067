#!/bin/bash
# PR 22, chip call 9 (1 chip): both serving cells on the 160-block pool, two sets of
# four runs each (every run another seed), then one traced run of each.
python3 benchmark/tools/measure.py --tag c9m --sets 2 --runs 4 \
    serve-mistral7b-chat-steady serve-mistral7b-longprompt-closed
python3 benchmark/tools/measure.py --tag c9t --sets 1 --runs 1 --seed0 7 --trace 1 \
    serve-mistral7b-chat-steady serve-mistral7b-longprompt-closed
grep -h "^# serve: tick\|^# serve: window" chiprun_out/c9m/*.log | cut -c1-400
grep -h "^# serve: attention route\|^{" chiprun_out/c9t/*.log | cut -c1-3000
