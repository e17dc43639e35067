#!/bin/bash
# PR 27, chip call 1 (4 chips): chip_smoke.py on four chips from the tree git would commit
# (build/archive_check = `git archive $(git write-tree)`), then the ZeRO-3 x TP training
# cell, that tree against build/parent = `git archive 74eee09`: parent, change, change,
# parent with tracing off (a seed per pair), then the change traced, with its device
# operations by kind (pr26_trace_ops.py: the bucket chain is gone from the step).
out=/root/repo/chiprun_out/p27c1; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
( cd /root/repo/build/archive_check && python3 chip_smoke.py > $out/smoke4.log 2> $out/smoke4.err )
echo "chip_smoke on four chips: rc $? $(tail -1 $out/smoke4.log | cut -c1-600)"
run() {  # side seed trace
    ( cd /root/repo/build/$1 && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err )
    echo "$1 seed $2 trace $3: rc $? $(tail -1 $out/$1.s$2.t$3.log | cut -c1-3500)"
}
run parent 2700000011 0; run archive_check 2700000011 0; run archive_check 2700000012 0; run parent 2700000012 0
run archive_check 2700000013 1
python3 tools/chip_calls/pr26_trace_ops.py /root/repo/build/archive_check $cell 6 $out/change_trace_ops.json
grep -h "by scope\|^# train: .* steps in\|step-0 loss\|set-up" $out/*.log | cut -c1-1800
