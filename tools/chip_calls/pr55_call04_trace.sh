#!/bin/bash
# PR 55, chip call 4 (1 chip): the committed body with the 16 -> 32 merge side by side beside the form of call 3,
# then one traced run of the change and what its gdn/rule scope holds besides the two kernels.
out=/root/repo/chiprun_out/p55c4; mkdir -p $out
python3 tools/chip_calls/pr55_candidates.py committed bb-late-ssa-oddrows null 2> $out/forms.err | tee $out/forms.jsonl | cut -c1-400
C=serve-qwen3next-longchat-closed32
python3 benchmark/run.py --workload $C --seed 5500000021 --seconds 51 --trace 1 > $out/$C.change.s5500000021.t1.log 2> $out/$C.change.s5500000021.t1.err
echo "rc $? $(tail -1 $out/$C.change.s5500000021.t1.log | cut -c1-300)"
python3 tools/chip_calls/pr55_rule_ops.py $C > $out/rule_ops.txt 2> $out/rule_ops.err; cat $out/rule_ops.txt | cut -c1-200
python3 tools/chip_calls/scope_mixed.py $C > $out/scope_mixed.txt 2>> $out/rule_ops.err; cut -c1-900 $out/scope_mixed.txt
exit 0
