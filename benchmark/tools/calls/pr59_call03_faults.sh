#!/bin/bash
# PR 59, call 3: the seeding as committed (EXPERT_OUT 1, SHARED_OUT 1,
# ROUTER_SCALE 2, D_SCALE 0.5): the whole faults table at two seeds (one of
# them call 2's worst), then the clean check alone at five more.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr59; mkdir -p $out
timeout -s KILL 2400 python3 benchmark/tools/calls/pr59_faults.py 2147483759 5900000059 2>&1 | grep -v Warn | tee $out/call03_faults.txt | grep "^seed\|^clean\|^seeding\|stopping" | cut -c1-300
timeout -s KILL 900 python3 benchmark/tools/calls/pr59_faults.py ONLY=clean 5900000061 5900000062 4294967291 5900000064 3100000059 2>&1 | grep -v Warn | tee -a $out/call03_faults.txt | grep "^seed\|^clean" | cut -c1-300
