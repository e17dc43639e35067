#!/bin/bash
# PR 44, the last chip call (1 chip): build/archive_check = `git archive $(git write-tree)` of the final tree, the files
# the driver's checkout holds.  chip_smoke.py's phases, a process each under a limit of its own (every serving phase
# runs the tiled kernel; `kernels` is the self-test with the five new paged_prefill cases), then more pairs of the
# claimed cell, the change from the archive tree, in the order parent, change, change, parent, and one traced run of it.
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr44_call06_final.sh
out=/root/repo/chiprun_out/p44c6; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
for ph in serve moe gdn conv kernels; do
    timeout -s KILL 700 python3 -c "import faulthandler; faulthandler.dump_traceback_later(600, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$ph',)); json.dump(s, open('$out/chip_smoke.$ph.json', 'w'), indent=1)" > $out/chip_smoke.$ph.log 2> $out/chip_smoke.$ph.err
    echo "chip_smoke $ph: rc $? $(grep "^chip_smoke: $ph ok" $out/chip_smoke.$ph.log | cut -c1-400)"
done
python3 - <<PY
import json
k = json.load(open("$out/chip_smoke.kernels.json"))
print("kernels:", json.dumps({n: k["kernels"].get(n) for n in ("cases", "prefill_us")}))
print("max_err:", json.dumps({n: v for n, v in k["kernels"]["max_err"].items() if n.startswith("paged_prefill")}))
PY
T=serve-trinity-mixedlen-closed32
run() {  # side seed trace
    local dir=/root/repo/build/archive_check; [ $1 = change ] || dir=/root/repo/build/parent
    ( cd $dir && python3 benchmark/run.py --workload $T --seed $2 --seconds 51 --trace $3 > $out/$T.$1.s$2.t$3.log 2> $out/$T.$1.s$2.t$3.err )
    echo "$T $1 seed $2 trace $3: rc $? $(tail -1 $out/$T.$1.s$2.t$3.log | cut -c1-${4:-600})"
    grep -o "the longest: [0-9]* ms" $out/$T.$1.s$2.t$3.log
}
run parent 4400000061 0; run change 4400000061 0
run change 2147483999 0; run parent 2147483999 0
run parent 1618033988 0; run change 1618033988 0
run change 4400000064 0; run parent 4400000064 0
run change 4400000065 1 6000
# the Ouro cell once more: both runs of the change in call 5 drew a tick of 2.3-3.3 s (the parent one of 0.9 s)
T=serve-ouro-reason-closed8
run change 4400000066 0; run parent 4400000066 0
run parent 4400000067 0; run change 4400000067 0
