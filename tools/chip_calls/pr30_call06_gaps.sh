#!/bin/bash
# PR 30, chip call 6 (1 chip; call 5 was this script with every seed in one process: it ran out of
# device memory at the third seed, after two lines): tools/chip_calls/pr30_logit_gaps.py on the OLMoE
# cell's configuration, a process a seed: the runner's logits check with the decode walk, the dense
# read and the gather beside each other on one set of weights; the other four seeds calls 2-4 ran
# this cell on, then twelve of their own.  First a probe (ran as build/pr30/bias_probe.py; kept as tools/chip_calls/pr30_bias_probe.py):
# float32 -> bf16 and exp inside a Mosaic kernel against XLA's, and the signed error of the walk and
# the dense read against an exact float32 attention.
out=/root/repo/chiprun_out/p30c6; mkdir -p $out
python3 build/pr30/bias_probe.py > $out/bias.log 2> $out/bias.err; echo "bias rc $?"; cat $out/bias.log
for seed in 3000000071 3000000091 3000000112 3000000113 3000000201 3000000202 3000000203 3000000204 \
        3000000205 3000000206 3000000207 3000000208 3000000209 3000000210 3000000211 3000000212; do
    python3 tools/chip_calls/pr30_logit_gaps.py $seed >> $out/gaps.log 2>> $out/gaps.err
    echo "rc $? $(grep "^seed $seed" $out/gaps.log)"
done
