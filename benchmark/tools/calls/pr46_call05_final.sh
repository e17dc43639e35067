#!/bin/bash
# PR 46, call 5 (1 chip): the committed files alone (build/archive_check =
# `git archive $(git write-tree)` of the final tree): the new cell on six
# seeds tracing off in one call (the spread), then `--trace 1` on the new
# cell and on one accepted cell that shares the slot pool; then the parent
# (build/parent, this PR's BENCHMARK.json and benchmark/ laid over it) on
# that accepted cell with `--trace 1`: the benchmark as this PR leaves it
# has to run on a program that lacks what this PR adds.
#   bash benchmark/tools/calls/pr46_call05_final.sh p46c5 <seed> x6
out=/root/repo/chiprun_out/$1; shift; mkdir -p $out
new=serve-jamba2-reason-closed256; old=serve-qwen3next-longchat-closed32
run() {  # dir side cell seed trace [chars]
    ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 \
        > $out/$3.$2.s$4.t$5.log 2> $out/$3.$2.s$4.t$5.err )
    echo "$3 $2 seed $4 trace $5: rc $? $(tail -1 $out/$3.$2.s$4.t$5.log | cut -c1-${6:-700})"
}
a=/root/repo/build/archive_check
for seed in "$@"; do run $a archive $new $seed 0; done
run $a archive $new $(( $1 + 7 )) 1 9000
grep -h "^# ssm\|^# device ms\|^# launches: program\|^# serve: window\|^# serve: prefill" $out/$new.archive.s$(( $1 + 7 )).t1.log | cut -c1-1500
run $a archive $old $(( $1 + 8 )) 1 6000
run /root/repo/build/parent parent $old $(( $1 + 8 )) 1 6000
