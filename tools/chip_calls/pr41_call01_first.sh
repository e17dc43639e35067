#!/bin/bash
# PR 41, chip call 1 (1 chip): the walk before the cells.  (a) decode_read_case on every cell's pool in the form each tree
# stores it: build/parent = `git archive 428ceb4` ([rows, Hkv, D], the masked product) against the working tree (the flat
# row, a dot a KV head); (b) the two-segment batch compiled at 128- and 64-wide heads on the flat row; (c) one pair of runs
# of the claimed cell, tracing off, parent then change on one seed.
out=/root/repo/chiprun_out/p41c1; mkdir -p $out
cd /root/repo
python3 tools/chip_calls/pr41_walk_probe.py build/parent $out/walk.parent.json mistral7b olmoe qwen3next trinity 2> $out/walk.parent.err
python3 tools/chip_calls/pr41_walk_probe.py . $out/walk.change.json 2> $out/walk.change.err
python3 - <<'PY' 2> $out/two_segment.err
import jax.numpy as jnp
from deepspeed_tpu.inference.v2.kernels.blocked_flash import two_segment_case
for kw in ({}, {"tight_pool": True}, {"d": 64}):
    got, want, real = two_segment_case(**kw)
    err = float(jnp.max(jnp.abs(got[real].astype(jnp.float32) - want[real].astype(jnp.float32))))
    print("two_segment", kw, "max_err", err, flush=True)
PY
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3500)"
    grep -h "token gap p50\|logits vs\|program(s) built" $out/$1.$2.s$3.t$4.log | cut -c1-600
}
T=serve-trinity-mixedlen-closed32
run $T parent 4100000011 0; run $T change 4100000011 0
