#!/bin/bash
# PR 60, call 4 (1 chip), the committed files alone (build/archive_check = `git archive $(git write-tree)`) beside the
# parent (build/parent = `git archive 5be9b94`), most needed first: (a) the claimed cell, three more untraced pairs on
# seeds over 2**31; (b) chip_smoke.py's `moe` phase (the grouped path against the dense composition on the chip) under a
# limit of its own; (c) one untraced pair a cell in Moonlight, LFM2 and Trinity.
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr60_call04_final.sh
out=/root/repo/chiprun_out/p60c4; mkdir -p $out
export CHANGE=/root/repo/build/archive_check
test -d $CHANGE/deepspeed_tpu || exit 1
SEEDS=3 TRACED=0 bash /root/repo/tools/chip_calls/pr60_cells.sh p60c4 2200000060 serve-qwen3next-longchat-closed32
( cd $CHANGE && timeout -s KILL 600 python3 -c "import faulthandler; faulthandler.dump_traceback_later(550, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('moe',)); json.dump(s, open('$out/chip_smoke.moe.json', 'w'), indent=1)" > $out/chip_smoke.moe.log 2> $out/chip_smoke.moe.err )
echo "chip_smoke moe: rc $? $(grep "^chip_smoke: moe" $out/chip_smoke.moe.log | cut -c1-600)"
SEEDS=1 TRACED=0 bash /root/repo/tools/chip_calls/pr60_cells.sh p60c4 6000000040 serve-moonlight-longdoc-closed64 serve-lfm2-agent-closed128 serve-trinity-mixedlen-closed32
exit 0
