#!/bin/bash
# PR 36, chip call 3 (1 chip): build/archive_check = `git archive $(git write-tree)`, the tree as committed but for this
# call's numbers, beside build/parent = `git archive 2699b65`: chip_smoke.py (its self-test's `gmm_share` now times six
# shapes), then the claimed cell serve-lfm2-agent-closed128, tracing off, four more pairs in the order parent, change,
# change, parent (two of the seeds large), then a traced run a side on one seed.
out=/root/repo/chiprun_out/p36c3; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 400 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell side seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-700)"
    grep -h "token gap p50\|logits vs" $out/$1.$2.s$3.t$4.log | cut -c1-400
}
L=serve-lfm2-agent-closed128
run $L parent 3600000041 0; run $L archive_check 3600000041 0; run $L archive_check 2147483999 0; run $L parent 2147483999 0
run $L parent 1618033988 0; run $L archive_check 1618033988 0; run $L archive_check 3600000044 0; run $L parent 3600000044 0
run $L parent 3600000051 1; run $L archive_check 3600000051 1
