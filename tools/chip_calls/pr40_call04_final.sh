#!/bin/bash
# PR 40, chip call 4 (1 chip): build/archive_check = `git archive $(git write-tree)`, the tree as committed but for this
# call's numbers, beside build/parent = `git archive b8b83c2`: chip_smoke.py, then the claimed cell
# serve-lfm2-agent-closed128, tracing off, four more pairs in the order parent, change, change, parent (two of the seeds
# large), then one traced run of the committed tree.
out=/root/repo/chiprun_out/p40c4; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 400 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3300)"
    grep -h "token gap p50\|logits vs\|ticks in the window made\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-900
}
L=serve-lfm2-agent-closed128
run $L parent 4000000041 0; run $L archive_check 4000000041 0; run $L archive_check 2147483999 0; run $L parent 2147483999 0
run $L parent 1618033988 0; run $L archive_check 1618033988 0; run $L archive_check 4000000044 0; run $L parent 4000000044 0
run $L archive_check 4000000051 1
