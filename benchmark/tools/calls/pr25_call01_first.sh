#!/bin/bash
# PR 25, chip call 1 (1 chip): first contact of the routed-expert path with the chip.
# chip_smoke.py with its new moe phase (the grouped GEMM at 64 experts, the depth-2
# OLMoE-width engine grouped against dense); the runner's logits check at the published
# widths on three seeds with every routing recorded (pr25_routing_agreement.py); the new
# cell traced (cold: first compile of its six programs) and untraced (warm); the device
# time of each program by scope (tools/chip_calls/scope_mixed.py); and the parent
# (build/parent_overlay = `git archive 26bb99e` with this PR's BENCHMARK.json and
# benchmark/ laid over it, as the driver does) on the new cell, which must fail at once.
out=/root/repo/chiprun_out/p25c1; mkdir -p $out
cd /root/repo
python3 chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err
echo "chip_smoke rc $? $(tail -1 $out/chip_smoke.log | cut -c1-300)"
cp chiprun_out/chip_smoke.json $out/ 2>/dev/null
grep "moe ok" $out/chip_smoke.log | cut -c1-3000
python3 benchmark/tools/calls/pr25_routing_agreement.py 2500000011 2500000012 2500000013 \
    > $out/routing.log 2> $out/routing.err
echo "routing rc $?"; grep "^seed" $out/routing.log; tail -3 $out/routing.err | cut -c1-600
c=serve-olmoe-chat-closed32
run() {  # side seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent_overlay
    ( cd $dir; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $c --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err
      rc=$?; t1=$(date +%s%N)
      [ "$3" = 0 ] && cp bench_out/$c/window_seed$2.json $out/$1.s$2.window.json 2>/dev/null
      echo "$1 seed $2 trace $3: rc $rc wall $(( (t1 - t0) / 1000000 )) ms $(tail -1 $out/$1.s$2.t$3.log | cut -c1-3500)"
      [ $rc != 0 ] && tail -5 $out/$1.s$2.t$3.err | cut -c1-800 )
}
run change 2500000021 1
python3 tools/chip_calls/scope_mixed.py $c 2>&1 | cut -c1-1800
run change 2500000022 0
run parent 2500000022 0
grep -h "^# " $out/change.s2500000021.t1.log | cut -c1-1500
grep -h "^# " $out/change.s2500000022.t0.log | cut -c1-600
