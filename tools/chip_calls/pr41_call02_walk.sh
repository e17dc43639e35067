#!/bin/bash
# PR 41, chip call 2 (1 chip): call 1 read the flat walk with a dot a KV head 27-40% over the stored-form walk.  The
# same probe on the arithmetic that replaced it (one pair of dots a step, the queries block-diagonal over the row's lanes):
# parent, change, change with twice the table entries a step; then one run of the claimed cell on the change.
out=/root/repo/chiprun_out/p41c2; mkdir -p $out
cd /root/repo
python3 tools/chip_calls/pr41_walk_probe.py build/parent $out/walk.parent.json mistral7b olmoe qwen3next trinity 2> $out/walk.parent.err
python3 tools/chip_calls/pr41_walk_probe.py . $out/walk.change.json 2> $out/walk.change.err
python3 tools/chip_calls/pr41_walk_probe.py . $out/walk.change.step2.json step=2 mistral7b olmoe qwen3next trinity 2> $out/walk.change.step2.err
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-600
}
run serve-trinity-mixedlen-closed32 change 4100000012 0
