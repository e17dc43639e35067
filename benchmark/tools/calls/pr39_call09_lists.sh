#!/bin/bash
# PR 39, chip call 9 (1 chip): call 8's set of six read tpot_p50_ms at a spread of 6.4% (Python's
# quartiles), over the 3.5% ISSUE 39 set, so the cell no longer lists it end to end nor the six
# per-layer metrics that move it, and reports closed_tpot_p50_ms among the per-layer ones.  The
# tree as git would commit it with those lists: one traced run (every listed metric a number) and
# two more untraced seeds; the parent under these benchmark files on the new cell (must fail).
out=/root/repo/chiprun_out/p39c9; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo/build/archive_check
s=3900000511; t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 1 > $out/traced.s$s.log 2> $out/traced.s$s.err
echo "traced seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/traced.s$s.log | cut -c1-6000)"
grep -h "roofline\|logits vs\|set-up\|serve: window\|token gap" $out/traced.s$s.log | cut -c1-600
for s in 3900000512 2147483659; do
  t0=$(date +%s%N)
  python3 benchmark/run.py --workload $c --seed $s --seconds 51 --trace 0 > $out/run.s$s.log 2> $out/run.s$s.err
  echo "seed $s: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/run.s$s.log | cut -c1-900)"
  grep -h "logits vs\|set-up\|program(s) built in the window" $out/run.s$s.log | cut -c1-330
done
cd /root/repo/build/parent_overlay
t0=$(date +%s%N)
python3 benchmark/run.py --workload $c --seed 3900000560 --seconds 51 --trace 1 > $out/parent.log 2> $out/parent.err
echo "parent overlay on $c: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms"; tail -1 $out/parent.err | cut -c1-300
