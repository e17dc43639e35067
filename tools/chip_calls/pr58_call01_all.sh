#!/bin/bash
# PR 58, THE chip call (1 chip; chips were scarce, so one call carries everything, most needed first): the committed
# files alone (build/archive_check = `git archive $(git write-tree)`) beside the parent (build/parent = `git archive
# 42d22b6`).  (a) both delta-rule kernels alone at the Olmo-Hybrid cell's shape on the pool of head pairs and on the
# natural pool (`tools/kernel_selftest.py gdn_olmo`); (b) the claimed cell: two untraced pairs and one traced pair;
# (c) chip_smoke.py's `gdn` phase; (d) the control, `serve-qwen3next-longchat-closed32`: one untraced pair; (e) the
# claimed cell again: two untraced pairs on seeds over 2**31.  A seed a pair, one process a run.
out=/root/repo/chiprun_out/p58c1; mkdir -p $out
export CHANGE=/root/repo/build/archive_check
cd $CHANGE || exit 1
timeout -s KILL 900 python3 tools/kernel_selftest.py gdn_olmo > $out/gdn_olmo.json 2> $out/gdn_olmo.err
echo "gdn_olmo: rc $? $(tr -d '\n ' < $out/gdn_olmo.json | cut -c1-1500)"
SEEDS=2 TRACED=1 bash /root/repo/tools/chip_calls/pr58_cells.sh p58c1 5800000010 serve-olmohybrid-evalgen-closed128
timeout -s KILL 900 python3 -c "import faulthandler; faulthandler.dump_traceback_later(800, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('gdn',)); json.dump(s, open('$out/chip_smoke.gdn.json', 'w'), indent=1)" > $out/chip_smoke.gdn.log 2> $out/chip_smoke.gdn.err
echo "chip_smoke gdn: rc $? $(grep "^chip_smoke: gdn" $out/chip_smoke.gdn.log | cut -c1-400)"
SEEDS=1 TRACED=0 bash /root/repo/tools/chip_calls/pr58_cells.sh p58c1 5800000030 serve-qwen3next-longchat-closed32
SEEDS=2 TRACED=0 bash /root/repo/tools/chip_calls/pr58_cells.sh p58c1 2200000060 serve-olmohybrid-evalgen-closed128
exit 0
