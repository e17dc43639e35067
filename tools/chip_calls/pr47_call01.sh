#!/bin/bash
# PR 47, chip call 1 (1 chip): chip_smoke.py's `serve` phase (the scheduler on the chip, mixed-length requests, against
# the XLA route) under a limit of its own, then the claimed cell: two untraced pairs and one traced pair.
#   chiprun --timeout 3500 -- bash tools/chip_calls/pr47_call01.sh
out=/root/repo/chiprun_out/p47c1; mkdir -p $out
timeout -s KILL 600 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('serve',)); json.dump(s, open('$out/chip_smoke.serve.json', 'w'), indent=1, default=str)" > $out/chip_smoke.serve.log 2> $out/chip_smoke.serve.err
echo "chip_smoke serve: rc $? $(tail -2 $out/chip_smoke.serve.log | cut -c1-600)"
SEEDS=2 TRACED=1 bash tools/chip_calls/pr47_cells.sh p47c1 4700000010 serve-jamba2-reason-closed256
