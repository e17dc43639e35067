#!/bin/bash
# PR 36, chip call 2 (1 chip): the committed rule once more in the probe (Moonlight's gate / up now takes the whole
# N = 1408 under a limit of its own, after call 1's `whole_n` form), then the three other cells whose programs hold the
# kernel, the working tree against build/parent = `git archive 2699b65`, tracing off, order parent, change, change,
# parent on two seeds each, then one traced run of the change in each.
out=/root/repo/chiprun_out/p36c2; mkdir -p $out
( python tools/chip_calls/pr36_probe.py change > $out/probe.log 2> $out/probe.err ); echo "probe rc $?"; grep -h '^{' $out/probe.log | cut -c1-2400
run() {  # cell side seed trace
    local dir=/root/repo; [ $2 = change ] || dir=/root/repo/build/$2
    ( cd $dir && python3 benchmark/run.py --workload $1 --seed $3 --seconds 51 --trace $4 \
        > $out/$1.$2.s$3.t$4.log 2> $out/$1.$2.s$3.t$4.err )
    echo "$1 $2 seed $3 trace $4: rc $? $(tail -1 $out/$1.$2.s$3.t$4.log | cut -c1-3000)"
    grep -h "token gap p50\|logits vs\|launches: program\|starved" $out/$1.$2.s$3.t$4.log | cut -c1-900
}
pair() {  # cell seed seed
    run $1 parent $2 0; run $1 change $2 0; run $1 change $3 0; run $1 parent $3 0
}
pair serve-moonlight-longdoc-closed64 3600000021 3600000022
pair serve-olmoe-chat-closed32 3600000023 2147484001
pair serve-qwen3next-longchat-closed32 3600000025 3600000026
run serve-moonlight-longdoc-closed64 change 3600000031 1
run serve-olmoe-chat-closed32 change 3600000032 1
run serve-qwen3next-longchat-closed32 change 3600000033 1
