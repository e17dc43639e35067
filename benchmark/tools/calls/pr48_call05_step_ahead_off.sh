#!/bin/bash
# PR 48, chip call 5 (1 chip): the request phases under the scheduling of PR 47's parent (the decode tick in which
# a row ends by length sends no step ahead: pr48_step_ahead_off.py), one traced run in each of the four cells whose
# `closed_ttft_p50_ms` rose most in PR 47.  Then two more traced runs of the change as it is in the Moonlight cell,
# whose `closed_ttft_p50_ms` differs by a quarter between runs of one program: which part moves.
#   chiprun --timeout 3400 -- bash benchmark/tools/calls/pr48_call05_step_ahead_off.sh
out=/root/repo/chiprun_out/p48c5; mkdir -p $out; n=4800000070
for cell in serve-jamba2-reason-closed256 serve-olmoe-chat-closed32 serve-ouro-reason-closed8 serve-mistral7b-longprompt-closed; do
    n=$((n + 1)); log=$out/$cell.ahead_off.s$n.t1.log
    python3 benchmark/tools/calls/pr48_step_ahead_off.py --workload $cell --seed $n --seconds 51 --trace 1 > $log 2> ${log%.log}.err
    echo "== $cell step ahead off, seed $n trace 1: rc $? $(grep -v '^#' $log | tail -1 | cut -c1-3000)"
    grep -h '^# serve: window\|^# serve: token gap\|^# first token' $log | cut -c1-900
done
bash benchmark/tools/calls/pr48_cells.sh p48c5 4800000075 traced serve-moonlight-longdoc-closed64 serve-moonlight-longdoc-closed64
exit 0
