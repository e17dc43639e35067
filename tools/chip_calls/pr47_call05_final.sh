#!/bin/bash
# PR 47, the last chip call (1 chip): build/archive_check = `git archive $(git write-tree)` of the final tree, the files
# the driver's checkout holds.  chip_smoke.py's `serve` phase under a limit of its own, then the claimed cell with the
# change from the archive tree: two untraced pairs (one seed over 2**31) and a traced run of the change.
#   chiprun --timeout 2400 -- bash tools/chip_calls/pr47_call05_final.sh
out=/root/repo/chiprun_out/p47c5; mkdir -p $out
( cd /root/repo/build/archive_check && timeout -s KILL 600 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('serve',)); json.dump(s, open('$out/chip_smoke.serve.json', 'w'), indent=1, default=str)" > $out/chip_smoke.serve.log 2> $out/chip_smoke.serve.err
  echo "chip_smoke serve (archive tree): rc $? $(grep '^chip_smoke: serve ok' $out/chip_smoke.serve.log | cut -c1-300)" )
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash tools/chip_calls/pr47_cells.sh p47c5 2147484100 serve-jamba2-reason-closed256
cd /root/repo/build/archive_check && python3 benchmark/run.py --workload serve-jamba2-reason-closed256 --seed 4700000113 --seconds 51 --trace 1 \
    > $out/serve-jamba2-reason-closed256.change.s4700000113.t1.log 2> $out/serve-jamba2-reason-closed256.change.s4700000113.t1.err
echo "traced change (archive tree): rc $? $(grep -v '^#' $out/serve-jamba2-reason-closed256.change.s4700000113.t1.log | tail -1 | cut -c1-3000)"
