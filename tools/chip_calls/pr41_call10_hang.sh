#!/bin/bash
# PR 41, chip call 10 (1 chip), after the review: tools/chip_calls/pr41_hang_probe.py, a process a variant, killed at 100 s.
out=${OUT:-/root/repo/chiprun_out/p41c10}; mkdir -p $out
for v in "$@"; do
    name=${v// /_}
    ( timeout -s KILL 100 python3 tools/chip_calls/pr41_hang_probe.py $v > $out/$name.log 2> $out/$name.err ); rc=$?
    echo "== $v: rc $rc"; grep "^PROBE" $out/$name.log | cut -c1-160
    grep -A14 "most recent call first" $out/$name.err | grep "File" | grep -v site-packages | head -3
done
