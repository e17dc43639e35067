#!/bin/bash
# PR 55, chip call 5 (1 chip): the committed files alone (build/archive_check = `git archive $(git write-tree)` of the
# final tree): chip_smoke.py's `gdn` phase in a process of its own under a limit, the committed kernel beside the
# null body and the parent's at the cell's shape, then the claimed cell: six untraced pairs and one traced pair,
# the change = the archive, the parent = build/parent (`git archive 8767a2b`), a seed a pair (two over 2**31).
out=/root/repo/chiprun_out/p55c5; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
timeout -s KILL 900 python3 -c "import faulthandler; faulthandler.dump_traceback_later(800, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('gdn',)); json.dump(s, open('$out/chip_smoke.gdn.json', 'w'), indent=1)" > $out/chip_smoke.gdn.log 2> $out/chip_smoke.gdn.err
echo "chip_smoke gdn: rc $? $(grep "^chip_smoke: gdn" $out/chip_smoke.gdn.log | cut -c1-400)"
(python3 tools/chip_calls/pr55_candidates.py committed null parent 2> $out/forms.err | tee $out/forms.jsonl | cut -c1-300)
CHANGE=/root/repo/build/archive_check SEEDS=4 TRACED=1 bash /root/repo/tools/chip_calls/pr55_cells.sh p55c5 5500000030 serve-qwen3next-longchat-closed32
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash /root/repo/tools/chip_calls/pr55_cells.sh p55c5 2200000040 serve-qwen3next-longchat-closed32
exit 0
