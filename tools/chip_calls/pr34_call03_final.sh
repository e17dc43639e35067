#!/bin/bash
# PR 34, chip call 3 (1 chip): build/archive_check = `git archive $(git write-tree)`, the tree as committed but for this
# call's numbers, beside build/parent = `git archive 7202f98`: chip_smoke.py (its `gdn` and `mla` phases serve interleaved
# requests through tools/interleaved_logits.py), then the claimed cell serve-moonlight-longdoc-closed64, tracing off, four
# more pairs in the order parent, change, change, parent (two of the seeds large), two more traced runs of the change and
# one of the parent under this PR's benchmark files; last the probe again with its third way (the token vector polled
# for in a busy loop instead of waited for in `device_get`).
out=/root/repo/chiprun_out/p34c3; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 400 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window made\|starved" $out/$1.$2.s$3.t$4.log | cut -c1-1200
}
M=serve-moonlight-longdoc-closed64
run $M parent 3400000041 0; run $M archive_check 3400000041 0; run $M archive_check 2147483999 0; run $M parent 2147483999 0
run $M parent 1618033988 0; run $M archive_check 1618033988 0; run $M archive_check 3400000044 0; run $M parent 3400000044 0
run $M archive_check 3400000051 1; run $M archive_check 977312645 1
run $M parent_overlay 3400000053 1
( cd /root/repo/build/archive_check && python tools/chip_calls/pr34_fetch_ways.py --seed 3400000061 > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; grep -h '^{' $out/probe.log | cut -c1-1800
