#!/bin/bash
# PR 49, chip call 6 (1 chip): where the set-up of the GPT-2 cell goes, under cProfile, change and parent (a window of 2 s).
#   chiprun --timeout 1200 -- bash tools/chip_calls/pr49_call06_setup_profile.sh
out=/root/repo/chiprun_out/p49c6; mkdir -p $out
for side in change parent; do
    dir=/root/repo; [ $side = change ] || dir=/root/repo/build/parent
    ( cd $dir && python3 -m cProfile -o $out/$side.prof benchmark/run.py --workload train-gpt2large-d64-s1k \
        --seed 4900000031 --seconds 2 --trace 0 > $out/$side.log 2> $out/$side.err )
    echo "$side rc $? $(grep 'set-up' $out/$side.log)"
done
