#!/bin/bash
# PR 33, chip call 3 (1 chip): the tree as git would commit it (build/archive_check =
# `git archive $(git write-tree)`) beside build/parent = `git archive 0aeaccd`: chip_smoke.py,
# a traced run of each of the five serving cells on a third seed (every listed metric, the
# launch table, 0 launches without an execution, starved <= idle), and one untraced pair of
# the OLMoE cell (parent, change).
out=/root/repo/chiprun_out/p33c3; mkdir -p $out
( cd /root/repo/build/archive_check && python chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err )
echo "chip_smoke rc $? $(tail -c 300 $out/chip_smoke.log)"
cp /root/repo/build/archive_check/chiprun_out/chip_smoke.json $out/ 2>/dev/null
run() {  # cell tree seed trace
    ( cd /root/repo/build/$2 && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3800)"
    grep -h "token gap p50\|launches\|made .* launches\|set-up" $out/$1.$2.s$3.t$4.log | cut -c1-1900
    tail -2 $out/$1.$2.s$3.t$4.err | grep -v "warnings.warn\|hugepages" | cut -c1-400
}
s=3300000051
for c in serve-mistral7b-chat-steady serve-mistral7b-longprompt-closed serve-olmoe-chat-closed32 \
         serve-qwen3next-longchat-closed32 serve-moonlight-longdoc-closed64; do
  run $c archive_check $s 1; s=$((s + 1))
done
run serve-olmoe-chat-closed32 parent 3300000061 0; run serve-olmoe-chat-closed32 archive_check 3300000061 0
