#!/bin/bash
# PR 46, call 3 (1 chip): the parent on the new cell (it has no
# families/jamba.py and no such entry: does it exit non-zero at once?), then
# the change on the cell: tracing off on the seeds given, then one traced
# run on each of the last TRACED (default 1) seeds.
#   bash benchmark/tools/calls/pr46_call03_cell.sh p46c3 <seed> [<seed> ...]
out=/root/repo/chiprun_out/$1; shift; mkdir -p $out
cell=serve-jamba2-reason-closed256
run() {  # dir side seed trace
    ( cd $1 && python3 benchmark/run.py --workload $cell --seed $3 --seconds 51 --trace $4 \
        > $out/$cell.$2.s$3.t$4.log 2> $out/$cell.$2.s$3.t$4.err )
    echo "$cell $2 seed $3 trace $4: rc $? $(tail -1 $out/$cell.$2.s$3.t$4.log | cut -c1-${5:-1200})"
}
if [ -d /root/repo/build/parent ] && [ -z "$SKIP_PARENT" ]; then
    t0=$(date +%s); run /root/repo/build/parent parent $1 0
    echo "parent on the new cell: $(( $(date +%s) - t0 )) s; $(tail -2 $out/$cell.parent.s$1.t0.err | cut -c1-300)"
fi
for seed in "$@"; do run /root/repo change $seed 0; done
n=$#; t=${TRACED:-1}; [ "$t" = 0 ] && exit 0
for seed in "${@:$((n - t + 1))}"; do run /root/repo change $seed 1 12000; done
grep -h "^# " $out/$cell.change.s${@: -1}.t1.log | cut -c1-2500
