#!/bin/bash
# PR 41, chip call 7 (1 chip): build/archive_check = `git archive $(git write-tree)` of the final tree.  Call 6 showed
# chip_smoke.py's train and serve phases pass and its `moe` phase never return (calls 6b, 6c: the dense oracle's put
# program on a 4 MB pool, which XLA keeps in fast memory; the parent's returns).  With that phase's pool of a deployment's
# order: the phases whose programs this PR changes, each a process of its own under a limit (moe, gdn, kernels = the
# self-test), then one more pair of the claimed cell, the change from the archive tree.
out=/root/repo/chiprun_out/p41c7; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
for ph in moe gdn kernels; do
    timeout -s KILL 600 python3 -c "import faulthandler; faulthandler.dump_traceback_later(500, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$ph',)); json.dump(s, open('$out/chip_smoke.$ph.json', 'w'), indent=1)" > $out/chip_smoke.$ph.log 2> $out/chip_smoke.$ph.err
    echo "chip_smoke $ph: rc $? $(grep "^chip_smoke: $ph ok" $out/chip_smoke.$ph.log | cut -c1-300)"
done
T=serve-trinity-mixedlen-closed32
python3 benchmark/run.py --workload $T --seed 4100000026 --seconds 51 --trace 0 > $out/$T.archive.s4100000026.t0.log 2> $out/$T.archive.s4100000026.t0.err
echo "$T archive seed 4100000026: rc $? $(tail -1 $out/$T.archive.s4100000026.t0.log | cut -c1-600)"
( cd /root/repo/build/parent && python3 benchmark/run.py --workload $T --seed 4100000026 --seconds 51 --trace 0 > $out/$T.parent.s4100000026.t0.log 2> $out/$T.parent.s4100000026.t0.err )
echo "$T parent seed 4100000026: rc $? $(tail -1 $out/$T.parent.s4100000026.t0.log | cut -c1-600)"
