#!/bin/bash
# PR 26, chip call 3 (4 chips): the tree git would commit (build/archive_check =
# `git archive $(git write-tree)`) against build/parent on two more seeds (parent, change,
# change, parent), the committed tree traced with the device operations by kind
# (pr26_trace_ops.py), and a second reading of overlap_comm off beside on, on one seed.
out=/root/repo/chiprun_out/p26c3; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
run() {  # side seed trace
    ( cd /root/repo/build/$1 && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err )
    echo "$1 seed $2 trace $3: rc $? $(tail -1 $out/$1.s$2.t$3.log | cut -c1-3500)"
}
run parent 2600000031 0; run archive_check 2600000031 0; run archive_check 2600000032 0; run parent 2600000032 0
run archive_check 2600000033 1
python3 tools/chip_calls/pr26_trace_ops.py /root/repo/build/archive_check $cell 6 $out/change_trace_ops.json
run overlap_off 2600000034 0; run archive_check 2600000034 0
grep -h "by scope\|^# train: .* steps in\|step-0 loss\|set-up" $out/*.log | cut -c1-1800
