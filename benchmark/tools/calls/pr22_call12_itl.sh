#!/bin/bash
# PR 22, chip call 12 (1 chip, the last 2.9 chip-minutes): one chat run of the final
# code on seed 300, to set the exact token-gap median (itl_p50_ms) beside the
# tick-interval stand-in re-computed for call 11's run on the same seed.
python3 benchmark/tools/measure.py --tag c12m --sets 1 --runs 1 --seed0 300 \
    serve-mistral7b-chat-steady
grep -h "^# serve: token gap\|^# serve: window\|set-up" chiprun_out/c12m/*.log | cut -c1-420
