#!/bin/bash
# PR 55, chip call 9 (1 chip): where the warm set-up of the Qwen3-Next cell goes, under cProfile, change (the committed
# files) and parent, a window of 2 s, twice each (the first pair warms whatever the profiler changes in the cache key).
out=/root/repo/chiprun_out/p55c9; mkdir -p $out
for i in 1 2; do for side in change parent; do
    dir=/root/repo/build/archive_check; [ $side = change ] || dir=/root/repo/build/parent
    ( cd $dir && python3 -m cProfile -o $out/$side.$i.prof benchmark/run.py --workload serve-qwen3next-longchat-closed32 \
        --seed 5500000071 --seconds 2 --trace 0 > $out/$side.$i.log 2> $out/$side.$i.err )
    echo "$side $i rc $? $(grep -o 'shape ladder.*' $out/$side.$i.log) | $(grep -o 'set-up.*' $out/$side.$i.log)"
done; done
python3 - <<'PY'
import pstats
for side in ("change", "parent"):
    print("=====", side)
    p = pstats.Stats(f"/root/repo/chiprun_out/p55c9/{side}.2.prof")
    p.sort_stats("cumulative").print_stats(60)
PY
exit 0
