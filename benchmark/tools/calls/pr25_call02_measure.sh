#!/bin/bash
# PR 25, chip call 2 (1 chip): the moe phase of chip_smoke.py again (call 1 found the
# 8-slot engine's 520-row forwards on the XLA composition: routed rows are now padded to
# whole tiles); the new cell with its pre-roll cut from 10 s to 6 s: six untraced runs on
# six new seeds, two traced runs with the device time of every program by scope; and one
# run of each Mistral serving cell, parent (build/parent = `git archive 26bb99e`) and
# change on the same seed, parent / change / change / parent.
out=/root/repo/chiprun_out/p25c2; mkdir -p $out
cd /root/repo
python3 -c "
import json, chip_smoke
s = chip_smoke.run(phases=('moe',))
print(json.dumps(s['moe'])[:6000])" > $out/smoke_moe.log 2> $out/smoke_moe.err
echo "chip_smoke moe rc $? $(tail -1 $out/smoke_moe.log | cut -c1-3500)"; tail -2 $out/smoke_moe.err | cut -c1-500
o=serve-olmoe-chat-closed32; l=serve-mistral7b-longprompt-closed; c=serve-mistral7b-chat-steady
run() {  # side cell seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir; t0=$(date +%s%N)
      python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err
      rc=$?; t1=$(date +%s%N)
      [ "$4" = 0 ] && cp bench_out/$2/window_seed$3.json $out/$1.$2.s$3.window.json 2>/dev/null
      echo "$1 $2 seed $3 trace $4: rc $rc wall $(( (t1 - t0) / 1000000 )) ms $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
      [ $rc != 0 ] && tail -5 $out/$1.$2.s$3.t$4.err | cut -c1-800 )
}
for s in 2500000031 2500000032 2500000033 2500000034 2500000035 2500000036; do run change $o $s 0; done
run change $o 2500000041 1
python3 tools/chip_calls/scope_mixed.py $o 2>&1 | cut -c1-1800
run change $o 2500000042 1
run parent $l 2500000051 0; run change $l 2500000051 0
run change $c 2500000052 0; run parent $c 2500000052 0
grep -h "logits vs\|set-up\|token gap\|window " $out/change.$o.*.log | cut -c1-700
grep -h "^# " $out/change.$o.s2500000041.t1.log | cut -c1-1500
