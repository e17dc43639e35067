#!/bin/bash
# PR 36, chip call 1 (1 chip): the tile rule's picks with microseconds a call in four forms (pr36_probe.py: the parent's
# rule, its row tile with the new column tile, the committed rule, the whole N as one column tile under a raised VMEM
# limit; the six shapes of kernel_selftest.GMM_SHARE_CELLS), then the claimed cell serve-lfm2-agent-closed128: the
# working tree against build/parent = `git archive 2699b65`, tracing off, order parent, change, change, parent on two
# seeds, then one traced run of the change.
out=/root/repo/chiprun_out/p36c1; mkdir -p $out
( python tools/chip_calls/pr36_probe.py > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; grep -h '^{' $out/probe.log | cut -c1-2400
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|launches\|ticks in the window made\|starved\|device ms by scope" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
L=serve-lfm2-agent-closed128
run $L parent 3600000011 0; run $L change 3600000011 0; run $L change 3600000012 0; run $L parent 3600000012 0
run $L change 3600000013 1
