#!/bin/bash
# PR 26, chip call 2 (1 chip): the GPT-2-Large training cell, which runs the changed
# _make_micro_grads at ZeRO-1 on one device: parent, change, change, parent with tracing
# off (a seed per pair).  Its compiled step equals the parent's (AOT, PERF.md section 6),
# so this is the check that nothing moves where the mechanism cannot engage.
out=/root/repo/chiprun_out/p26c2; mkdir -p $out
cell=train-gpt2large-d64-s1k
run() {  # side seed trace
    local dir=/root/repo; [ "$1" != change ] && dir=/root/repo/build/$1
    ( cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err )
    echo "$1 seed $2 trace $3: rc $? $(tail -1 $out/$1.s$2.t$3.log | cut -c1-2500)"
}
run parent 2600000021 0; run change 2600000021 0; run change 2600000022 0; run parent 2600000022 0
