#!/bin/bash
# PR 34, chip call 1 (1 chip): the probe (the same mixed tick fetched both ways, Moonlight and Mistral widths), then the
# claimed cell serve-moonlight-longdoc-closed64: the working tree against build/parent = `git archive 7202f98`, tracing
# off, order parent, change, change, parent on two seeds; then one traced run a side (the parent under this PR's benchmark
# files, build/parent_overlay, as the driver traces it).
out=/root/repo/chiprun_out/p34c1; mkdir -p $out
( python tools/chip_calls/pr34_fetch_ways.py --seed 3400000001 > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; grep -h '^{' $out/probe.log | cut -c1-1500
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window made\|starved" $out/$1.$2.s$3.t$4.log | cut -c1-1200
}
M=serve-moonlight-longdoc-closed64
run $M parent 3400000011 0; run $M change 3400000011 0; run $M change 3400000012 0; run $M parent 3400000012 0
run $M change 3400000013 1
run $M parent_overlay 3400000014 1
