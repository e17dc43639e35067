#!/bin/bash
# PR 64, the chip calls.  Every run goes through benchmark/tools/calls/pr64_with_metrics.py (run.py with the five
# set-up metrics appended in memory, the runner's end-to-end values logged as commentary), one process a run; its whole
# output goes to chiprun_out/<tag>/<cell>.<side>.<what>.log, and one line a run is echoed: exit code, the printed
# setup_s, the set-up metrics (traced runs), the end-to-end values.
#   setup <seconds> <cell>...   a COLD run (the call's machine starts with no compile cache; COLD_DIR=1 gives the cell
#                               an emptied cache directory of its own besides) then a WARM one, both traced: the cell's
#                               set-up by part, cold and warm
#   cost <cell>...              what tracing costs ON: parent (build/parent = `git archive 8a9ba0b`, this PR's benchmark
#                               files laid over it as the driver does) beside the change, one warming run a side, then
#                               --trace 0 and --trace 1 at 51 s in the order parent 0, change 0, change 1, parent 1
#   chiprun --timeout 3000 -- bash tools/chip_calls/pr64_cells.sh p64c1 6400000010 setup 20 serve-olmoe-chat-closed32
# The committed files alone: `git archive $(git write-tree) | tar -x -C build/archive_check`, then
#   chiprun -- env OUT=/root/repo/chiprun_out bash build/archive_check/tools/chip_calls/pr64_cells.sh <tag> <seed> setup 20 <cell>
cd "$(dirname "$0")/../.."
root=$PWD; out=${OUT:-$root/chiprun_out}/$1; n=$2; mode=$3; shift 3; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR='$JAX_COMPILATION_CACHE_DIR' $(python3 -c 'import jax; print(jax.__version__)')"
line() {  # log file -> the numbers of one run
    python3 - "$1" <<'PY'
import json, re, sys
text = open(sys.argv[1]).read().splitlines()
said = [l for l in text if l.startswith("# ")]
last = next((l for l in reversed(text) if l.startswith("{")), "{}")
res = json.loads(last)
m = {k: v["value"] for k, v in res.get("metrics", {}).items()}
setup = next((re.search(r"set-up ([0-9.]+) s", l).group(1) for l in said if ": set-up " in l), "?")
e2e = next((l.split("end to end: ")[1] for l in said if "end to end: " in l), "?")
built = next((re.search(r"(\d+) program\(s\) built in the\s+window", l).group(1) for l in said
              if "built in the" in l and "window" in l), "?")
print(f"correct {res.get('correct')} failed {res.get('failed')} setup_s {setup} built_in_window {built} | "
      + " ".join(f"{k} {m[k]:.3f}" for k in sorted(m) if k.startswith("setup_")) + f" | {e2e}")
PY
}
run() {  # dir cell seed seconds trace label
    ( cd $1 && timeout -s KILL 1800 python3 benchmark/tools/calls/pr64_with_metrics.py --workload $2 --seed $3 \
        --seconds $4 --trace $5 > $out/$2.$6.log 2> $out/$2.$6.err )
    echo "$2 $6 seed $3 seconds $4 trace $5: rc $? $(line $out/$2.$6.log 2>&1 | tail -1)"
}
if [ $mode = setup ]; then
    seconds=$1; shift
    for cell in "$@"; do
        if [ "${COLD_DIR:-0}" = 1 ]; then
            rm -rf $root/build/cold_cache_$cell; mkdir -p $root/build/cold_cache_$cell
            export JAX_COMPILATION_CACHE_DIR=$root/build/cold_cache_$cell
        fi
        n=$((n + 1)); run $root $cell $n $seconds 1 change.cold
        n=$((n + 1)); run $root $cell $n $seconds 1 change.warm
        grep -h "^# set-up" $out/$cell.change.warm.log | cut -c1-1200
    done
else
    test -d build/parent/deepspeed_tpu || exit 2
    cp BENCHMARK.json build/parent/BENCHMARK.json; cp -r benchmark/. build/parent/benchmark/
    for cell in "$@"; do
        n=$((n + 1)); run $root/build/parent $cell $n 5 0 parent.warming; run $root $cell $n 5 0 change.warming
        n=$((n + 1)); run $root/build/parent $cell $n 51 0 parent.t0; run $root $cell $n 51 0 change.t0
        n=$((n + 1)); run $root $cell $n 51 1 change.t1; run $root/build/parent $cell $n 51 1 parent.t1
    done
fi
exit 0
