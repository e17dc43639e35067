#!/bin/bash
# PR 55, chip call 10 (1 chip): the warm set-up of the claimed cell by phase, no profiler, change (the committed files)
# and parent alternating, three times each after one warming run a side.
out=/root/repo/chiprun_out/p55c10; mkdir -p $out
for i in 0 1 2 3; do for side in change parent; do
    dir=/root/repo/build/archive_check; [ $side = change ] || dir=/root/repo/build/parent
    ( cd $dir && python3 /root/repo/tools/chip_calls/pr55_setup_phases.py serve-qwen3next-longchat-closed32 5500000081 \
        > $out/$side.$i.log 2> $out/$side.$i.err )
    echo "$side $i rc $? $(grep -o 'shape ladder.*' $out/$side.$i.log) | $(tail -1 $out/$side.$i.log | cut -c1-700)"
done; done
exit 0
