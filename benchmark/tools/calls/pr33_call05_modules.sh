#!/bin/bash
# PR 33, chip call 5 (1 chip), after the review: executions are read from the profile's "XLA
# Modules" line (lib/xplane_modules.py), the idle figures leave the stretch's one longest gap
# out, idle_at_dispatch is gone from the program.  The tree of `git archive $(git write-tree)`
# (build/archive_check) beside build/parent = `git archive 0aeaccd` and build/parent_overlay =
# the parent with this PR's BENCHMARK.json and benchmark/ laid over it (as the driver traces it).
# 1. every serving cell traced through pr33_probe.py (the result line, the log, and a dump the
#    readers can be run on again); 2. a second traced seed of the three MoE cells; 3. the chat
#    cell traced on the parent under the overlay (seed of the chat run of 1.) and untraced on
#    parent and change: what tracing costs when it is on.
out=/root/repo/chiprun_out/p33c5; mkdir -p $out
show() {  # log err
    echo "rc $1 $(tail -1 $2 | cut -c1-3800)"
    grep -h "token gap p50\|collections over\|launches\|made .* launches\|set-up" $2 | cut -c1-2000
    tail -2 $3 | grep -v "warnings.warn\|hugepages" | cut -c1-400
}
probe() {  # cell seed
    ( cd /root/repo/build/archive_check && python3 benchmark/tools/calls/pr33_probe.py $1 $2 $out \
        > $out/$1.probe.s$2.log 2> $out/$1.probe.s$2.err )
    echo "== $1 probe seed $2: $(show $? $out/$1.probe.s$2.log $out/$1.probe.s$2.err)"
}
run() {  # cell tree seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "== $1 $2 seed $3 trace $4: $(show $? $out/$1.$2.s$3.t$4.log $out/$1.$2.s$3.t$4.err)"
}
probe serve-moonlight-longdoc-closed64 3300000081
probe serve-qwen3next-longchat-closed32 3300000082
probe serve-olmoe-chat-closed32 3300000083
probe serve-mistral7b-chat-steady 3300000084
probe serve-mistral7b-longprompt-closed 3300000085
run serve-moonlight-longdoc-closed64 archive_check 3300000091 1
run serve-qwen3next-longchat-closed32 archive_check 3300000092 1
run serve-olmoe-chat-closed32 archive_check 3300000093 1
c=serve-mistral7b-chat-steady
run $c parent_overlay 3300000084 1
run $c parent 3300000094 0; run $c archive_check 3300000094 0
