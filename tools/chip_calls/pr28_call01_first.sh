#!/bin/bash
# PR 28, chip call 1 (1 chip): chip_smoke.py on the working tree, then one traced run of
# each serving cell: is the step ahead engaged (chat_decode_ahead_pct / decode_ahead_pct),
# what is left of the idle share, and does every accepted per-layer metric still report.
out=/root/repo/chiprun_out/p28c1; mkdir -p $out
python3 chip_smoke.py > $out/smoke1.log 2> $out/smoke1.err
echo "chip_smoke on one chip: rc $? $(tail -1 $out/smoke1.log | cut -c1-600)"
run() {  # cell seed trace
    python3 benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace $3 \
        > $out/$1.s$2.t$3.log 2> $out/$1.s$2.t$3.err
    echo "$1 seed $2 trace $3: rc $? $(tail -1 $out/$1.s$2.t$3.log | cut -c1-4000)"
    grep -h "ticks in the window\|gmm roofline\|do not divide\|token gap p50\|program(s) built" $out/$1.s$2.t$3.err | cut -c1-700
}
run serve-mistral7b-chat-steady 2800000011 1
run serve-olmoe-chat-closed32 2800000012 1
run serve-mistral7b-longprompt-closed 2800000013 1
