#!/bin/bash
# PR 29, chip call 1 (1 chip): first contact of the linear-attention path with the chip.
# chip_smoke.py with its new gdn phase (two interleaved requests at the published widths,
# one period deep) and the two new kernels in the self-test; four interleaved requests on
# the cell's own engine against their reference forwards (pr29_interleaved.py); the new
# cell traced (cold) and untraced; and the parent (build/parent_overlay = `git archive
# 8c72620` with this PR's BENCHMARK.json and benchmark/ laid over it, as the driver does)
# on the new cell, which must fail at once.
out=/root/repo/chiprun_out/p29c1; mkdir -p $out
cd /root/repo
python3 chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err
echo "chip_smoke rc $? $(tail -1 $out/chip_smoke.log | cut -c1-300)"
cp chiprun_out/chip_smoke.json $out/ 2>/dev/null
grep "gdn ok\|kernels ok" $out/chip_smoke.log | cut -c1-3000
tail -5 $out/chip_smoke.err | cut -c1-1500
python3 benchmark/tools/calls/pr29_interleaved.py 2900000001 > $out/inter.log 2> $out/inter.err
echo "interleaved rc $?"; grep "^seed\|^interleaved" $out/inter.log; tail -3 $out/inter.err | cut -c1-900
c=serve-qwen3next-longchat-closed32
run() {  # side seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent_overlay
    ( cd $dir; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $c --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err
      rc=$?; t1=$(date +%s%N)
      echo "$1 seed $2 trace $3: rc $rc wall $(( (t1 - t0) / 1000000 )) ms $(tail -1 $out/$1.s$2.t$3.log | cut -c1-3500)"
      [ $rc != 0 ] && tail -5 $out/$1.s$2.t$3.err | cut -c1-1200 )
}
run change 2900000021 1
run change 2900000022 0
run parent 2900000022 0
grep -h "^# " $out/change.s2900000021.t1.log | cut -c1-1800
grep -h "^# " $out/change.s2900000022.t0.log | cut -c1-700
