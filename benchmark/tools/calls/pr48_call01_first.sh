#!/bin/bash
# PR 48, chip call 1 (1 chip): chip_smoke.py's `serve` phase (the scheduler on the chip against the XLA route) under
# a limit of its own; then the first traced runs of the change: the Jamba2 cell (256 rows, the most host-bound), the
# chat cell (the one open loop) and the OLMoE cell, and one traced run of the parent under this PR's benchmark files.
#   chiprun --timeout 1800 -- bash benchmark/tools/calls/pr48_call01_first.sh
out=/root/repo/chiprun_out/p48c1; mkdir -p $out
timeout -s KILL 600 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('serve',)); json.dump(s, open('$out/chip_smoke.serve.json', 'w'), indent=1, default=str)" > $out/chip_smoke.serve.log 2> $out/chip_smoke.serve.err
echo "chip_smoke serve: rc $? $(tail -2 $out/chip_smoke.serve.log | cut -c1-600)"
bash benchmark/tools/calls/pr48_cells.sh p48c1 4800000010 traced serve-jamba2-reason-closed256 serve-mistral7b-chat-steady serve-olmoe-chat-closed32
CHANGE=/root/repo/build/parent bash benchmark/tools/calls/pr48_cells.sh p48c1 4800000020 traced serve-olmoe-chat-closed32
