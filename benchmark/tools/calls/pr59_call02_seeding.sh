#!/bin/bash
# PR 59, call 2: where the routed experts' scale has to stand.  Call 1 read
# the clean program at 0.033-0.073 with EXPERT_OUT 8 (a routing flip between
# the bf16 engine and the float32 reference swaps an expert whose output is
# eight times a unit one).  The clean check and the fault it trades against
# (softmax_all) at three seeds, EXPERT_OUT 2, 3 and 4.
cd "$(dirname "$0")/../../.."
out=$PWD/chiprun_out/pr59; mkdir -p $out
for e in 2 3 4; do
  timeout -s KILL 1500 python3 benchmark/tools/calls/pr59_faults.py ONLY=clean,softmax_all EXPERT_OUT=$e 5900000059 2147483759 5900000061 2>&1 | grep -v Warn | tee -a $out/call02_seeding.txt | grep "^seed\|^clean\|^seeding" | cut -c1-300
done
