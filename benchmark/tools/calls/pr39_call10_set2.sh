#!/bin/bash
# PR 39, chip call 10 (1 chip): a second set of six of the new cell on the tree as git would
# commit it (the driver admits a new cell on two sets of six), seeds of their own.
out=/root/repo/chiprun_out/p39c10; mkdir -p $out
c=serve-trinity-mixedlen-closed32
cd /root/repo/build/archive_check
t0=$(date +%s)
python3 benchmark/tools/measure.py --tag p39c10m --sets 1 --runs 6 --seed0 3900000601 \
    --trace 0 $c > $out/measure6.log 2> $out/measure6.err
echo "measure (6) rc $? wall $(( $(date +%s) - t0 )) s"; tail -12 $out/measure6.log | cut -c1-600
cp -r chiprun_out/p39c10m /root/repo/chiprun_out/ 2>/dev/null
grep -h "logits vs\|set-up\|program(s) built in the window" chiprun_out/p39c10m/*.log | cut -c1-330
