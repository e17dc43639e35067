#!/bin/bash
# PR 41, chip call 6b (1 chip): call 6 (chip_smoke.py on the archive tree) stopped answering in the `moe` phase, after the
# OLMoE-width engine (8 sequence slots, a table 3 entries wide, a pool of 8 blocks) was built, and was killed at the call's
# limit.  Each step here is a process of its own under a time limit: the phase's attention calls alone
# (tools/chip_calls/pr41_moe_repro.py), then the phase itself, in the committed tree and in two variants of `_row_walk`
# (build/v_nogate: no `pl.when` around the row's work; build/v_first: the form calls 2-4 ran).
out=/root/repo/chiprun_out/p41c6b; mkdir -p $out
step() {  # name dir seconds command...
    local name=$1 dir=$2 secs=$3; shift 3
    ( cd $dir && timeout -s KILL $secs "$@" > $out/$name.log 2> $out/$name.err ); local rc=$?
    echo "$name: rc $rc $(grep -v "^\[20\|^WARNING" $out/$name.log | tail -3 | cut -c1-400)"
}
MOE='import chip_smoke, json; s = chip_smoke.run(phases=("moe",)); print(json.dumps(s["moe"]["ragged_moe_serve"]))'
for v in "$@"; do
    d=/root/repo/build/$v; [ $v = change ] && d=/root/repo/build/archive_check
    step repro.$v $d 150 python3 /root/repo/tools/chip_calls/pr41_moe_repro.py
    step moe.$v $d 240 python3 -c "$MOE"
done
