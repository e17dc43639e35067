#!/bin/bash
# PR 41, chip call 12 (1 chip), after the review: build/archive_check = `git archive $(git write-tree)` of the final tree
# (the two-index row insert, `_verify_kernel` on flat blocks, chip_smoke.py's moe phase back on its 8-block pool).
# In order of what must not be lost: the moe phase (the regression of calls 6-10), the claimed cell and the chat cell from
# the archive tree on seeds whose parent runs are in calls 7 and 5b, the verify read on both trees, the self-test.
out=/root/repo/chiprun_out/p41c12; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
smoke() {
    timeout -s KILL $2 python3 -c "import faulthandler; faulthandler.dump_traceback_later($2 - 20, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('$1',)); json.dump(s, open('$out/chip_smoke.$1.json', 'w'), indent=1)" > $out/chip_smoke.$1.log 2> $out/chip_smoke.$1.err
    echo "chip_smoke $1: rc $? $(grep "^chip_smoke: $1 ok" $out/chip_smoke.$1.log | cut -c1-300)"
}
cell() {  # workload seed
    python3 benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace 0 > $out/$1.archive.s$2.t0.log 2> $out/$1.archive.s$2.t0.err
    echo "$1 archive seed $2: rc $? $(tail -1 $out/$1.archive.s$2.t0.log | cut -c1-700)"
}
smoke moe 200
cell serve-trinity-mixedlen-closed32 4100000026
cell serve-mistral7b-chat-steady 4100000062
python3 tools/chip_calls/pr41_verify_probe.py /root/repo/build/archive_check $out/verify_read.change.json 2> $out/verify.change.err | grep "^verify" | cut -c1-300
( cd /root/repo/build/parent && python3 /root/repo/build/archive_check/tools/chip_calls/pr41_verify_probe.py /root/repo/build/parent $out/verify_read.parent.json 2> $out/verify.parent.err | grep "^verify" | cut -c1-300 )
smoke kernels 420
ls $out | head -30
