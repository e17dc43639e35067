#!/bin/bash
# PR 33, chip call 6 (1 chip): the final tree of `git archive $(git write-tree)`
# (build/archive_check): the long-prompt cell traced again (call 5's run of it met the stalled
# tick of ROADMAP A2: 4.5 s at the window's end, and its profile holds 1.38 s of device events)
# and the Moonlight cell traced on a third seed.
out=/root/repo/chiprun_out/p33c6; mkdir -p $out
run() {  # cell seed
    ( cd /root/repo/build/archive_check && python3 benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace 1 \
        > $out/$1.s$2.t1.log 2> $out/$1.s$2.t1.err )
    echo "== $1 seed $2: rc $? $(tail -1 $out/$1.s$2.t1.log | cut -c1-3800)"
    grep -h "token gap p50\|launches\|set-up" $out/$1.s$2.t1.log | cut -c1-2000
    tail -2 $out/$1.s$2.t1.err | grep -v "warnings.warn\|hugepages" | cut -c1-400
}
run serve-mistral7b-longprompt-closed 3300000095
run serve-moonlight-longdoc-closed64 3300000096
