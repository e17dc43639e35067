#!/bin/bash
# PR 23, chip call 5 (4 chips): the ZeRO-3 x TP cell traced once more, after the scope
# metrics of the training cells took the norms in (Llama's norms are modules beside
# self_attn and mlp, not inside them): do the four scope metrics now hold >= 80% of
# other_device_ms_step?
out=/root/repo/chiprun_out/p23c5; mkdir -p $out
cell=train-mistral7b-z3tp-s4k
python3 benchmark/run.py --workload $cell --seed 2000000042 --seconds 51 --trace 1 \
    > $out/change.$cell.s2000000042.t1.log 2> $out/change.$cell.s2000000042.t1.err
echo "rc $?"; grep -h "by scope\|kernels matching\|steps in" $out/*.log | cut -c1-2500
tail -1 $out/change.$cell.s2000000042.t1.log | cut -c1-2500
