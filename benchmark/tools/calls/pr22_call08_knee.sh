#!/bin/bash
# PR 22, chip call 8 (1 chip): knee sweep of the chat cell on the 160-block pool,
# the cell's own 45 s window and arrival process, 3 seeds a rate, one process.
mkdir -p chiprun_out/c8k
python3 benchmark/tools/knee_sweep.py --tag c8k --cell serve-mistral7b-chat-steady \
    --seeds 11,12,13 2.0 2.5 3.0 3.5 > chiprun_out/c8k/stdout.log 2> chiprun_out/c8k/stderr.log
rc=$?
grep -v "^# serve: planned" chiprun_out/c8k/stdout.log | cut -c1-900 | tail -60
tail -5 chiprun_out/c8k/stderr.log
exit $rc
