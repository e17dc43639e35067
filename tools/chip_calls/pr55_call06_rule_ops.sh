#!/bin/bash
# PR 55, chip call 6 (1 chip): one traced run of the committed files (build/archive_check) and what the gdn/rule scope
# of its step programs holds besides the two kernels, now that the per-row columns are laid out head-major.
out=/root/repo/chiprun_out/p55c6; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
C=serve-qwen3next-longchat-closed32
python3 benchmark/run.py --workload $C --seed 5500000051 --seconds 51 --trace 1 > $out/$C.archive.s5500000051.t1.log 2> $out/$C.archive.s5500000051.t1.err
echo "rc $? $(tail -1 $out/$C.archive.s5500000051.t1.log | cut -c1-300)"
python3 tools/chip_calls/pr55_rule_ops.py $C > $out/rule_ops.txt 2> $out/rule_ops.err; head -20 $out/rule_ops.txt | cut -c1-200
python3 tools/chip_calls/scope_mixed.py $C > $out/scope_mixed.txt 2>> $out/rule_ops.err; cut -c1-600 $out/scope_mixed.txt
exit 0
