#!/bin/bash
# PR 48, chip call 6 (1 chip): the tree as git would commit it, unpacked under build/archive_check
# (`git archive $(git write-tree) | tar -x -C build/archive_check`): chip_smoke.py's `serve` phase, the Jamba2 and
# the chat cell traced, then untraced pairs parent / change: three in the Jamba2 cell (the sides alternating), one in the
# chat cell.
#   chiprun --timeout 3000 -- bash benchmark/tools/calls/pr48_call06_final.sh
out=/root/repo/chiprun_out/p48c6; mkdir -p $out; tree=/root/repo/build/archive_check
( cd $tree && timeout -s KILL 600 python3 -c "import chip_smoke, json; s = chip_smoke.run(phases=('serve',)); json.dump(s, open('$out/chip_smoke.serve.json', 'w'), indent=1, default=str)" > $out/chip_smoke.serve.log 2> $out/chip_smoke.serve.err )
echo "chip_smoke serve (archive tree): rc $? $(tail -2 $out/chip_smoke.serve.log | cut -c1-300)"
export CHANGE=$tree
bash $tree/benchmark/tools/calls/pr48_cells.sh p48c6 4800000080 traced serve-jamba2-reason-closed256 serve-mistral7b-chat-steady
n=2147483990
pair() {  # cell first-side second-side: one untraced pair on a seed of its own
    n=$((n + 1))
    for side in $2 $3; do
        dir=$tree; [ $side = change ] || dir=/root/repo/build/parent
        log=$out/$1.$side.s$n.t0.log
        ( cd $dir && python3 benchmark/run.py --workload $1 --seed $n --seconds 51 --trace 0 > $log 2> ${log%.log}.err )
        echo "== $1 $side seed $n trace 0: rc $? $(grep -v '^#' $log | tail -1 | cut -c1-600)"
        grep -h '^# serve: token gap' $log | cut -c1-260
    done
}
pair serve-jamba2-reason-closed256 parent change
pair serve-jamba2-reason-closed256 change parent
pair serve-jamba2-reason-closed256 parent change
pair serve-mistral7b-chat-steady change parent
exit 0
