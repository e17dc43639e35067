#!/bin/bash
# PR 24, chip call 3 (1 chip): the tree as git would commit it (build/archive_check =
# `git archive $(git write-tree)`) against the parent (build/parent, see call 2).
# chip_smoke.py from the archive; the long-prompt cell: six runs of the change on six new
# seeds (the spread of the claimed metrics) with the parent before and after on two of
# them; the chat cell: change, parent, change; both cells traced from the archive; and the
# device time of each put program by scope (build/scope_mixed.py, a one-off that is not
# committed: benchmark.lib.xplane_ops + readers.scope_ms.scope_key over the newest trace).
out=/root/repo/chiprun_out/p24c3; mkdir -p $out
( cd /root/repo/build/archive_check && python3 chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err
  echo "chip_smoke (archive) rc $? $(tail -1 $out/chip_smoke.log | cut -c1-300)"
  cp chiprun_out/chip_smoke.json $out/ 2>/dev/null )
run() {  # side cell seed trace
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err
      rc=$?
      [ "$4" = 0 ] && cp bench_out/$2/window_seed$3.json $out/$1.$2.s$3.window.json 2>/dev/null
      echo "$1 $2 seed $3 trace $4: rc $rc $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-2600)" )
}
l=serve-mistral7b-longprompt-closed; c=serve-mistral7b-chat-steady
run parent $l 2400000051 0
for s in 2400000051 2400000052 2400000053 2400000054 2400000055 2400000056; do run change $l $s 0; done
run parent $l 2400000056 0
run change $c 2400000061 0; run parent $c 2400000061 0; run change $c 2400000062 0
run change $l 2400000053 1
( cd /root/repo/build/archive_check && python3 /root/repo/build/scope_mixed.py $l ) 2>&1 | cut -c1-1500
run change $c 2400000062 1
( cd /root/repo/build/archive_check && python3 /root/repo/build/scope_mixed.py $c ) 2>&1 | cut -c1-1500
grep -h "token gap\|host ms per tick\|by scope\|set-up\|logits vs" $out/*.log | cut -c1-1200
