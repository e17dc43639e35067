#!/bin/bash
# PR 42, chip calls 2 and 3 (1 chip): one cell of every serving configuration (all six trace code this PR moved), the
# parent (build/parent = `git archive c74b965`) beside the change, tracing off, in the order parent, change, change,
# parent on two seeds, then (TRACED cells) one traced run of the change: do the benchmark's readers still find every
# scope and kernel by name.  No gain is claimed: the question is whether any end-to-end metric left its bound.
#   bash tools/chip_calls/pr42_call02_cells.sh p42c2 4200000020 serve-mistral7b-chat-steady serve-trinity-mixedlen-closed32 serve-lfm2-agent-closed128
#   bash tools/chip_calls/pr42_call02_cells.sh p42c3 4200000030 serve-olmoe-chat-closed32 serve-qwen3next-longchat-closed32 serve-moonlight-longdoc-closed64
out=/root/repo/chiprun_out/$1; n=$2; shift 2; mkdir -p $out
change=${CHANGE:-/root/repo}
run() {  # cell side seed trace
    local dir=$change; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-${5:-900})"
}
for cell in "$@"; do
    n=$((n + 1)); run $cell parent $n 0; run $cell change $n 0
    n=$((n + 1)); run $cell change $n 0; run $cell parent $n 0
    case " ${TRACED:-serve-trinity-mixedlen-closed32 serve-lfm2-agent-closed128 serve-qwen3next-longchat-closed32} " in
        *" $cell "*) n=$((n + 1)); run $cell change $n 1 6000;;
    esac
done
