#!/bin/bash
# PR 41, chip call 6c (1 chip): call 6b: the `moe` phase of chip_smoke.py stops answering in every variant of `_row_walk`
# while its attention calls alone pass; in call 6 it stopped in the first (grouped) engine, in 6b in the second (the dense
# oracle).  Where does Python wait, and does the parent's phase run today?  Each step a process of its own under a limit,
# with faulthandler's traceback of every thread after 100 s.
out=/root/repo/chiprun_out/p41c6c; mkdir -p $out
step() {  # name dir seconds command...
    local name=$1 dir=$2 secs=$3; shift 3
    ( cd $dir && timeout -s KILL $secs "$@" > $out/$name.log 2> $out/$name.err ); local rc=$?
    echo "$name: rc $rc $(grep -v "^\[20\|^WARNING" $out/$name.log | tail -2 | cut -c1-300)"
    grep -A14 "most recent call first" $out/$name.err | grep "File" | head -12
}
MOE='import faulthandler, sys; faulthandler.dump_traceback_later(100, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=("moe",)); print(json.dumps(s["moe"]["ragged_moe_serve"])[:300])'
step moe.parent /root/repo/build/parent 200 python3 -c "$MOE"
step moe.change1 /root/repo/build/archive_check 200 python3 -c "$MOE"
step moe.change2 /root/repo/build/archive_check 200 python3 -c "$MOE"
