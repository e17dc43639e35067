#!/bin/bash
# PR 24, chip call 4 (1 chip): what sets the decode tick's two modes (15.2 or 17.4 ms for a
# whole run, on the parent too; it reached the chat cell once in call 3)?  Twelve short runs
# (8 s windows) of both serving cells, change and parent, through build/diag_modes.py (a
# one-off that is not committed: benchmark/run.py with Server.window wrapped to print the CPU
# and NUMA node the process runs on and the device addresses of the KV pool and the weights).
out=/root/repo/chiprun_out/p24c4; mkdir -p $out
run() {  # side cell seed
    local dir=/root/repo/build/archive_check; [ "$1" = parent ] && dir=/root/repo/build/parent
    ( cd $dir && python3 /root/repo/build/diag_modes.py --workload $2 --seed $3 --seconds 8 --trace 0 \
        > $out/$1.$2.s$3.log 2> $out/$1.$2.s$3.err
      echo "$1 $2 seed $3: rc $? $(grep -h 'token gap' $out/$1.$2.s$3.log | cut -c1-60)"
      grep -h '# diag' $out/$1.$2.s$3.log | cut -c1-700 )
}
l=serve-mistral7b-longprompt-closed; c=serve-mistral7b-chat-steady
for i in 1 2 3 4; do
  run change $c 240000007$i; run change $l 240000008$i; run parent $l 240000008$i
done
lscpu | head -25
