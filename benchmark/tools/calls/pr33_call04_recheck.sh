#!/bin/bash
# PR 33, chip call 4 (1 chip): after call 3's Qwen3-Next stretch came back with a hole (the
# profile lost 2.6 s of device events: 169 launches without an execution, device_idle_pct 70.8)
# the reader marks a partial execution in the middle of a stretch as it marks one at its ends.
# The tree of `git archive $(git write-tree)` again: the Qwen3-Next cell and the chat cell traced.
out=/root/repo/chiprun_out/p33c4; mkdir -p $out
run() {  # cell tree seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3800)"
    grep -h "token gap p50\|launches\|made .* launches\|set-up" $out/$1.$2.s$3.t$4.log | cut -c1-1900
    tail -2 $out/$1.$2.s$3.t$4.err | grep -v "warnings.warn\|hugepages" | cut -c1-400
}
run serve-qwen3next-longchat-closed32 archive_check 3300000071 1
run serve-mistral7b-chat-steady archive_check 3300000072 1
