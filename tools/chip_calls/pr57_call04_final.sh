#!/bin/bash
# PR 57, chip call 4 (1 chip): the committed files alone (build/archive_check = `git archive $(git write-tree)` of the
# final tree): chip_smoke.py's `gdn` and `conv` phases, each in a process of its own under a limit, then the claimed
# cell: five untraced pairs, the change = the archive, the parent = build/parent (`git archive 722867d`), a seed a
# pair (two over 2**31).
out=/root/repo/chiprun_out/p57c4; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
for phase in gdn conv; do
    timeout -s KILL 900 python3 -c "import faulthandler; faulthandler.dump_traceback_later(800, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$phase',)); json.dump(s, open('$out/chip_smoke.$phase.json', 'w'), indent=1)" > $out/chip_smoke.$phase.log 2> $out/chip_smoke.$phase.err
    echo "chip_smoke $phase: rc $? $(grep "^chip_smoke: $phase" $out/chip_smoke.$phase.log | cut -c1-400)"
done
CHANGE=/root/repo/build/archive_check SEEDS=3 TRACED=0 bash /root/repo/tools/chip_calls/pr57_cells.sh p57c4 5700000040 serve-olmohybrid-evalgen-closed128
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash /root/repo/tools/chip_calls/pr57_cells.sh p57c4 2200000050 serve-olmohybrid-evalgen-closed128
exit 0
