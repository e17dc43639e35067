#!/bin/bash
# PR 64, call E: is the Ouro cell's 45 s warm shape ladder (11-15 s of LOWERING for three of its programs, call C) this
# PR's?  The PARENT alone (build/parent = `git archive 8a9ba0b`, this PR's benchmark files laid over it): one warming run
# (cold: its programs are in no cache), then --trace 1 and --trace 0 warm; the ladder's seconds are the harness's own line.
cd "$(dirname "$0")/../.."
test -d build/parent/deepspeed_tpu || exit 2
cp BENCHMARK.json build/parent/BENCHMARK.json; cp -r benchmark/. build/parent/benchmark/
out=$PWD/chiprun_out/p64cE; mkdir -p $out; cell=serve-ouro-reason-closed8
run() {  # label seed seconds trace
    ( cd build/parent && timeout -s KILL 1500 python3 benchmark/tools/calls/pr64_with_metrics.py --workload $cell \
        --seed $2 --seconds $3 --trace $4 > $out/$cell.parent.$1.log 2> $out/$cell.parent.$1.err )
    echo "parent $1 seed $2 trace $4: rc $? $(grep -h 'shape ladder\|set-up [0-9.]* s;' $out/$cell.parent.$1.log | cut -c1-160 | tr '\n' ' ')"
}
run warming 6400000101 5 0
run t1 6400000102 20 1
run t0 6400000103 20 0
exit 0
