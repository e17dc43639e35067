#!/bin/bash
# PR 24, chip call 5 (4 chips): chip_smoke.py on the 2x2 host, the one path of this PR that
# exists only across chips: the two-segment forward per shard under TP=4 (8 q / 2 kv heads a
# shard; each put program must route its tiles to _prefill_kernel, its single-token rows to
# _decode_kernel) beside ZeRO-3 x TP training, which this PR does not touch.
python3 chip_smoke.py
