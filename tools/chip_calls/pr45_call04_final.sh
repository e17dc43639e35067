#!/bin/bash
# PR 45, the last chip call (1 chip): build/archive_check = `git archive $(git write-tree)` of the final tree, the files
# the driver's checkout holds.  chip_smoke.py's `mla` phase (the Moonlight-width engine through the expanded read) and
# `kernels` (the self-test, with `latent_prefill` and the new `latent_prefill_cell`), a process each under a limit of
# its own; then three more pairs of the claimed cell, the change from the archive tree (parent, change, change, parent,
# parent, change; one seed over 2**31), and one traced run of each side.
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr45_call04_final.sh
out=/root/repo/chiprun_out/p45c4; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
for ph in mla kernels; do
    timeout -s KILL 800 python3 -c "import faulthandler; faulthandler.dump_traceback_later(700, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$ph',)); json.dump(s, open('$out/chip_smoke.$ph.json', 'w'), indent=1)" > $out/chip_smoke.$ph.log 2> $out/chip_smoke.$ph.err
    echo "chip_smoke $ph: rc $? $(grep "^chip_smoke: $ph ok" $out/chip_smoke.$ph.log | cut -c1-400)"
done
python3 - <<PY
import json
k = json.load(open("$out/chip_smoke.kernels.json"))
print("kernels:", json.dumps({n: k["kernels"].get(n) for n in ("cases", "prefill_us")}))
print("max_err:", json.dumps({n: v for n, v in k["kernels"]["max_err"].items() if n.startswith("latent")}))
PY
cd /root/repo
CHANGE=/root/repo/build/archive_check SEEDS=3 bash tools/chip_calls/pr45_call02_cells.sh p45c4 2147483990 serve-moonlight-longdoc-closed64
