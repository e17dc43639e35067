#!/bin/bash
# PR 31, chip call 8 (1 chip), after the driver's first check refused the cell: a traced
# stretch of seed 1203555967 held no pure-decode tick, so the two per-decode-tick readings
# were left out of the line.  mla_read_ms_tick and mla_decode_roofline_pct now read every
# tick of the stretch (row_blocks on engine/build_batch beside read_blocks on decode).
# From build/archive_check (git archive of the staged tree): the driver's seed traced,
# two more traced, two untraced; then the PARENT with this PR's benchmark files laid over
# it (build/parent_overlay): the new cell fails at once, an accepted cell's traced run
# reports every accepted metric and none of the new ones.
out=/root/repo/chiprun_out/p31c8; mkdir -p $out
c=serve-moonlight-longdoc-closed64
one() {  # dir tag workload seed trace
  cd /root/repo/build/$1; t0=$(date +%s%N)
  timeout 600 python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 \
    > $out/$2.log 2> $out/$2.err
  echo "$2 seed $4 trace $5: rc $? wall $(( ($(date +%s%N) - t0) / 1000000 )) ms $(tail -1 $out/$2.log | cut -c1-2400)"
  grep -h "logits vs\|set-up\|mla \|under '/attn" $out/$2.log | cut -c1-220; tail -2 $out/$2.err | cut -c1-300
}
one archive_check t1a $c 1203555967 1
one archive_check t1b $c 2031455809 1
one archive_check t0a $c 1745203311 0
one archive_check t1c $c 977312645 1
one archive_check t0b $c 2147483999 0
one parent_overlay parent_new $c 3100000301 0
one parent_overlay parent_olmoe serve-olmoe-chat-closed32 1618033988 1
