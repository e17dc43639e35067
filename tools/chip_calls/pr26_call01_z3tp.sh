#!/bin/bash
# PR 26, chip call 1 (4 chips): the ZeRO-3 x TP training cell.  chip_smoke's train phase
# first (ZeRO-3 x TP through deepspeed_tpu.initialize against the XLA route), then parent,
# change, change, parent with tracing off (a seed per pair), the change traced, and the
# change with overlap_comm off (build/overlap_off = this tree with one key added to the
# runner's _ds_config; ROADMAP A1b).  build/parent = `git archive aa23320`; this PR adds
# nothing under benchmark/, so there is nothing to lay over it.
out=/root/repo/chiprun_out/p26c1; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
python3 -c "import json, chip_smoke; print(json.dumps(chip_smoke.run(phases=('train',))['train']))" \
    > $out/smoke_train.log 2> $out/smoke_train.err
echo "chip_smoke train phase: rc $? $(tail -1 $out/smoke_train.log | cut -c1-1500)"
run() {  # side seed trace
    local dir=/root/repo; [ "$1" != change ] && dir=/root/repo/build/$1
    ( cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err )
    echo "$1 seed $2 trace $3: rc $? $(tail -1 $out/$1.s$2.t$3.log | cut -c1-3500)"
}
run parent 2600000011 0; run change 2600000011 0; run change 2600000012 0; run parent 2600000012 0
run change 2600000013 1
run overlap_off 2600000012 0
grep -h "by scope\|kernels matching\|^# train: attention\|^# train: .* steps in\|step-0 loss" $out/*.log | cut -c1-1800
