#!/bin/bash
# PR 25, chip call 5 (1 chip): the tree as git would commit it after the review
# (build/archive_check = `git archive $(git write-tree)`), the cell's pre-roll back at
# ISSUE 25's 10 s.  The moe phase of chip_smoke.py (its tightened limits and the router
# floor); the parent with this PR's benchmark laid over it (build/parent_overlay) on the
# new cell, which must fail at once; the new cell: six untraced runs on six seeds, two
# traced runs with the device time of every program by scope, three more untraced seeds;
# and the accepted long-prompt cell traced on the parent under the overlay, which must
# still run with the files this PR adds.
out=/root/repo/chiprun_out/p25c5; mkdir -p $out
( cd /root/repo/build/archive_check && python3 -c "
import json, chip_smoke
s = chip_smoke.run(phases=('moe',))
print(json.dumps(s['moe'])[:6000])" > $out/smoke_moe.log 2> $out/smoke_moe.err
  echo "chip_smoke moe (archive) rc $? $(tail -1 $out/smoke_moe.log | cut -c1-3500)"; tail -2 $out/smoke_moe.err | cut -c1-500 )
o=serve-olmoe-chat-closed32; l=serve-mistral7b-longprompt-closed
run() {  # side cell seed trace; every line of the log stamped with the wall clock
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent_overlay
    ( cd $dir; t0=$(date +%s%N)
      python3 -u benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        2> $out/$1.$2.s$3.t$4.err | while IFS= read -r line; do
            echo "$(date +%s.%N | cut -c1-14) $line"; done > $out/$1.$2.s$3.t$4.log
      rc=${PIPESTATUS[0]}; t1=$(date +%s%N)
      [ "$4" = 0 ] && cp bench_out/$2/window_seed$3.json $out/$1.$2.s$3.window.json 2>/dev/null
      echo "$1 $2 seed $3 trace $4: rc $rc wall $(( (t1 - t0) / 1000000 )) ms from $(( t0 / 1000000 )) $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-2600)"
      [ $rc != 0 ] && tail -4 $out/$1.$2.s$3.t$4.err | cut -c1-600 )
}
run parent $o 2500000081 0
for s in 2500000081 2500000082 2500000083 2500000084 2500000085 2500000086; do run change $o $s 0; done
run change $o 2500000091 1
( cd /root/repo/build/archive_check && python3 tools/chip_calls/scope_mixed.py $o 2>&1 | cut -c1-1800 )
run change $o 2500000092 1
for s in 2500000087 2500000088 2500000089; do run change $o $s 0; done
run parent $l 2500000095 1
grep -h "1 x TPU\|InferenceEngineV2\|logits vs\|shape ladder\|pre-roll\|set-up\|token gap\|window " $out/change.$o.*.t0.log | cut -c1-420
grep -h "by scope\|gmm roofline\|host ms per tick" $out/change.$o.s2500000091.t1.log $out/change.$o.s2500000092.t1.log | cut -c1-1500
