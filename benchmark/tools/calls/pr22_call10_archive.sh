#!/bin/bash
# PR 22, chip call 10 (1 chip): the proof that what git commits is enough, the GPT-2
# cell under the median-over-groups rate, and the long-prompt cell printing
# total_tok_s.  build/archive_check holds `git archive $(git write-tree)` of the final
# tree (git-ignored, copied to the chip); everything below runs inside it.
cd build/archive_check || exit 9
python3 benchmark/tools/measure.py --tag c10m --sets 1 --runs 3 \
    train-gpt2large-d64-s1k serve-mistral7b-longprompt-closed
python3 benchmark/tools/measure.py --tag c10t --sets 1 --runs 1 --seed0 7 --trace 1 \
    train-gpt2large-d64-s1k
grep -h "^# train: .* steps in\|^# serve: window\|^# serve: tick" chiprun_out/c10m/*.log | cut -c1-420
grep -h "^{" chiprun_out/c10t/*.log | cut -c1-2500
mkdir -p /root/repo/chiprun_out && cp -r chiprun_out/c10m chiprun_out/c10t /root/repo/chiprun_out/
# and the refusal: BENCHMARK.json and benchmark/ alone
mkdir -p ../alone && cp -r BENCHMARK.json benchmark ../alone/ && cd ../alone && \
    python3 benchmark/run.py --workload train-gpt2large-d64-s1k --seed 1 --seconds 5 --trace 0 \
    > /root/repo/chiprun_out/c10m/alone.stdout 2> /root/repo/chiprun_out/c10m/alone.stderr
echo "alone rc $? stdout lines $(wc -l < /root/repo/chiprun_out/c10m/alone.stdout)"
tail -3 /root/repo/chiprun_out/c10m/alone.stderr
