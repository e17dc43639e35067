#!/bin/bash
# PR 61: lay this PR's BENCHMARK.json and benchmark/ over build/parent (a
# `git archive` of the parent commit 1d0c621), as the driver does before it
# runs a new cell, or a traced run, on the parent.
#   bash benchmark/tools/calls/pr61_overlay.sh
set -e
cd "$(dirname "$0")/../../.."
test -d build/parent/deepspeed_tpu
cp BENCHMARK.json build/parent/BENCHMARK.json
cp -r benchmark/. build/parent/benchmark/
