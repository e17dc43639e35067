#!/bin/bash
# PR 42, chip calls 1 and 4 (1 chip; call 4: `pr42_call01_smoke.sh p42c4 /root/repo/build/archive_check`): the tree after the move (inference/v2/modules/, the capability table), as it stands on
# disk.  Every phase of chip_smoke.py a process of its own under a limit (a program that never returns holds the call
# otherwise: PR 41), the self-test as the `kernels` phase.  Each prints its own "chip_smoke: <phase> ok" line.
out=/root/repo/chiprun_out/${1:-p42c1}; mkdir -p $out
cd ${2:-/root/repo} || exit 1
smoke() {
    timeout -s KILL $2 python3 -c "import faulthandler; faulthandler.dump_traceback_later($2 - 20, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$1',)); json.dump(s, open('$out/chip_smoke.$1.json', 'w'), indent=1)" > $out/chip_smoke.$1.log 2> $out/chip_smoke.$1.err
    echo "chip_smoke $1: rc $? $(grep "^chip_smoke: $1 ok" $out/chip_smoke.$1.log | cut -c1-400)"
}
smoke train 420; smoke serve 420; smoke moe 300; smoke gdn 420; smoke mla 420; smoke conv 420; smoke kernels 600
grep -h "attention_route" -A12 $out/chip_smoke.serve.json | head -40
