#!/bin/bash
# PR 48, chip call 3 (1 chip): what tracing costs in the Jamba2 cell (256 rows, the most host-bound), before and
# after the change: two rounds of parent untraced, parent traced, change traced, change untraced, a seed a round.
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr48_call03_cost_jamba2.sh
PAIRS=2 bash benchmark/tools/calls/pr48_cells.sh p48c3 4800000050 cost serve-jamba2-reason-closed256
