#!/bin/bash
# PR 30, chip call 3 (1 chip): the self-test's decode-read cases, then the working tree against build/parent = `git archive d83890a`,
# tracing off, order parent, change, change, parent, a seed per pair: the claimed cell first
# (serve-mistral7b-chat-steady, four more pairs), then two pairs of the long-prompt cell and one
# more of the OLMoE and the Qwen3-Next cell. Then the traced runs: the three other serving cells
# on the change, and the chat cell on the parent (no file under benchmark/ changes in this PR,
# so the parent under this PR's benchmark files is the parent).
out=/root/repo/chiprun_out/p30c3; mkdir -p $out
# first the self-test's three new cases alone (call 2 compiled each pool in as a constant: 280 s;
# they are arguments now), for the crossover table and its compile seconds
( python -c "
import json, sys, time
sys.path.insert(0, 'tools')
import kernel_selftest as k
t0 = time.time()
print(json.dumps({c: k.decode_read_case(c, 3e-2) for c in k.DECODE_READ_CELLS}), round(time.time() - t0, 1), 's')
" > $out/decode_read.log 2> $out/decode_read.err ); echo "decode_read rc $? $(tail -1 $out/decode_read.log)"
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|attention route\|device ms by scope" $out/$1.$2.s$3.t$4.log | cut -c1-1500
}
pair() {  # cell seed seed
    run $1 parent $2 0; run $1 change $2 0; run $1 change $3 0; run $1 parent $3 0
}
pair serve-mistral7b-chat-steady 3000000051 3000000052
pair serve-mistral7b-chat-steady 3000000053 3000000054
pair serve-mistral7b-longprompt-closed 3000000061 3000000062
run serve-olmoe-chat-closed32 change 3000000071 0; run serve-olmoe-chat-closed32 parent 3000000071 0
run serve-qwen3next-longchat-closed32 change 3000000081 0; run serve-qwen3next-longchat-closed32 parent 3000000081 0
run serve-olmoe-chat-closed32 change 3000000091 1
run serve-qwen3next-longchat-closed32 change 3000000092 1
run serve-mistral7b-longprompt-closed change 3000000093 1
run serve-mistral7b-chat-steady parent 3000000094 1
