#!/bin/bash
# PR 41, chip call 8 (4 chips): what exists only across chips: chip_smoke.py's serve phase at TP=4 from the archive tree
# (Mistral widths: the flat pool row [rows, 1024] split into four lane ranges of two KV heads, the walk and the tiled
# kernel on a shard's [rows, 256]), a process under a limit of its own.  Compiled for a described v5e 2x2 first
# (no chip): both kernels under shard_map, no pool-sized copy.
out=/root/repo/chiprun_out/p41c8; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
timeout -s KILL 420 python3 -c "import faulthandler; faulthandler.dump_traceback_later(360, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('serve',)); json.dump(s, open('$out/chip_smoke.serve.tp4.json', 'w'), indent=1)" > $out/chip_smoke.serve.tp4.log 2> $out/chip_smoke.serve.tp4.err
echo "chip_smoke serve on 4 chips: rc $? $(grep "^chip_smoke: serve ok" $out/chip_smoke.serve.tp4.log | cut -c1-900)"
