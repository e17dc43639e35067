#!/bin/bash
# PR 24, chip call 2 (1 chip): both serving cells, parent against change with tracing off
# (parent, change, change, parent; two seeds a cell), then both sides traced on one seed a
# cell.  build/parent holds `git archive b6ddf56` with BENCHMARK.json and benchmark/ of this
# PR laid over it (git-ignored, copied to the chip; this PR adds only these scripts there).
# The per-tick side files (bench_out/<cell>/window_seed<n>.json) come back too: the decode
# tick of the long-prompt cell reads 15.2 or 17.5 ms by run on either side.
out=/root/repo/chiprun_out/p24c2; mkdir -p $out
run() {  # side cell seed trace
    local dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err
      rc=$?
      [ "$4" = 0 ] && cp bench_out/$2/window_seed$3.json $out/$1.$2.s$3.window.json 2>/dev/null
      echo "$1 $2 seed $3 trace $4: rc $rc $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-2600)" )
}
l=serve-mistral7b-longprompt-closed; c=serve-mistral7b-chat-steady
run parent $l 2400000031 0; run change $l 2400000031 0; run change $l 2400000032 0; run parent $l 2400000032 0
run parent $c 2400000041 0; run change $c 2400000041 0; run change $c 2400000042 0; run parent $c 2400000042 0
run change $l 2400000032 1; run parent $l 2400000032 1
run change $c 2400000041 1; run parent $c 2400000041 1
grep -h "token gap\|host ms per tick\|by scope\|kernels matching\|shape ladder\|set-up\|logits vs" $out/*.log | cut -c1-1500
