#!/bin/bash
# PR 24, chip call 1 (1 chip): the two-segment layout's first time on the chip.
# chip_smoke.py (serve phase: every put program must route its tiles to _prefill_kernel and
# nothing to _kernel; kernel self-test with the two new two-segment cases), then the
# long-prompt cell traced and untraced on one seed and the chat cell untraced: does it
# serve, is every run correct, what do the mixed ticks cost now.
out=/root/repo/chiprun_out/p24c1; mkdir -p $out
python3 chip_smoke.py > $out/chip_smoke.log 2> $out/chip_smoke.err
echo "chip_smoke rc $? $(tail -2 $out/chip_smoke.log | cut -c1-600)"
grep -h "serve ok" $out/chip_smoke.log | grep -o '"attention_route": {[^}]*}[^}]*}[^}]*}[^}]*}[^}]*}[^}]*}[^}]*}' | cut -c1-900
run() {  # cell seed trace
    python3 benchmark/run.py --workload $1 --seed $2 --seconds 51 --trace $3 \
        > $out/change.$1.s$2.t$3.log 2> $out/change.$1.s$2.t$3.err
    echo "change $1 seed $2 trace $3: rc $? $(tail -1 $out/change.$1.s$2.t$3.log | cut -c1-2600)"
}
c=serve-mistral7b-chat-steady; l=serve-mistral7b-longprompt-closed
run $l 2400000011 1; run $l 2400000011 0
run $c 2400000021 0
grep -h "token gap\|host ms per tick\|by scope\|kernels matching\|attention route\|shape ladder\|logits vs" $out/change.*.log | cut -c1-1500
