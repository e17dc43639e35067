#!/bin/bash
# PR 23, chip call 3 (4 chips): the ZeRO-3 x TP training cell, parent against change
# with tracing off (one seed; the cell spreads 0.02-0.06%), then the change's traced
# run: flash forward / dq / dkv under shard_map, the scopes on a mesh.
out=/root/repo/chiprun_out/p23c3; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
run() {  # side seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 \
        > $out/$1.$cell.s$2.t$3.log 2> $out/$1.$cell.s$2.t$3.err )
    echo "$1 $cell seed $2 trace $3: rc $? $(tail -1 $out/$1.$cell.s$2.t$3.log | cut -c1-3000)"
}
run parent 2000000041 0; run change 2000000041 0; run change 2000000041 1
grep -h "by scope\|kernels matching\|^# train: attention\|^# train: .* steps in" $out/*.log | cut -c1-1500
