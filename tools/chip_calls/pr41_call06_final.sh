#!/bin/bash
# PR 41, chip call 6 (1 chip) and 7 (4 chips): the committed files are enough.  build/archive_check = `git archive
# $(git write-tree)` of the final tree.  One chip: chip_smoke.py there (its self-test times the walk on every cell's pool in
# the stored form, its serve phase checks the routes from the lowered text), then one more pair of the claimed cell, the
# parent from build/parent and the change from the same archive tree (the walk's once-a-row work was trimmed after call 3).  Four chips (`bash tools/chip_calls/pr41_call06_final.sh 4`): chip_smoke.py alone, for TP=4 serving on the
# flat row split into lane ranges.
n=${1:-1}; out=/root/repo/chiprun_out/p41c6; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
python3 chip_smoke.py > $out/chip_smoke.$n.log 2> $out/chip_smoke.$n.err; rc=$?
echo "chip_smoke on $n chip(s): rc $rc $(tail -c 600 $out/chip_smoke.$n.log)"
cp chiprun_out/chip_smoke.json $out/chip_smoke.$n.json 2>/dev/null || cp /root/repo/chiprun_out/chip_smoke.json $out/chip_smoke.$n.json
[ $n = 4 ] && exit $rc
T=serve-trinity-mixedlen-closed32
python3 benchmark/run.py --workload $T --seed 4100000026 --seconds 51 --trace 0 > $out/$T.archive.s4100000026.t0.log 2> $out/$T.archive.s4100000026.t0.err
echo "$T archive seed 4100000026: rc $? $(tail -1 $out/$T.archive.s4100000026.t0.log | cut -c1-600)"
( cd /root/repo/build/parent && python3 benchmark/run.py --workload $T --seed 4100000026 --seconds 51 --trace 0 > $out/$T.parent.s4100000026.t0.log 2> $out/$T.parent.s4100000026.t0.err )
echo "$T parent seed 4100000026: rc $? $(tail -1 $out/$T.parent.s4100000026.t0.log | cut -c1-600)"
exit $rc
