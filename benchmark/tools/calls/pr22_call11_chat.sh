#!/bin/bash
# PR 22, chip call 11 (1 chip): the chat cell on the final tree, a third set of three
# runs on seeds of their own (the spread behind the two 10% bounds; the final code).
python3 benchmark/tools/measure.py --tag c11m --sets 1 --runs 3 --seed0 300 \
    serve-mistral7b-chat-steady
grep -h "^# serve: tick\|^# serve: window" chiprun_out/c11m/*.log | cut -c1-420
