#!/bin/bash
# PR 53, chip call 6 (1 chip): the committed files alone (build/archive_check = `git archive $(git write-tree)` of the
# final tree): chip_smoke.py's `moe` phase (the grouped GEMM at 64 experts against `gmm_reference`, the engine's grouped
# path against the dense composition) in a process of its own under a limit, the probe's parent and committed forms
# through `gmm_share_case`, then the claimed cell twice more from the archive tree (one seed over 2**31 of another size), and two more pairs of
# the control cell (OLMoE: one of call 5's four runs met two stalled ticks of 2.1 s, ROADMAP A13), the change = the archive.
out=/root/repo/chiprun_out/p53c6; mkdir -p $out
cd /root/repo/build/archive_check || exit 1
timeout -s KILL 600 python3 -c "import faulthandler; faulthandler.dump_traceback_later(500, exit=False); import chip_smoke, json; s = chip_smoke.run(phases=('moe',)); json.dump(s, open('$out/chip_smoke.moe.json', 'w'), indent=1)" > $out/chip_smoke.moe.log 2> $out/chip_smoke.moe.err
echo "chip_smoke moe: rc $? $(grep "^chip_smoke: moe ok" $out/chip_smoke.moe.log | cut -c1-400)"
python3 tools/chip_calls/pr53_probe.py parent change 2> $out/probe.err | tee $out/probe.jsonl | cut -c1-200
C=serve-lfm2-agent-closed128
for seed in 5300000071 2200000072; do
    python3 benchmark/run.py --workload $C --seed $seed --seconds 51 --trace 0 > $out/$C.archive.s$seed.t0.log 2> $out/$C.archive.s$seed.t0.err
    echo "$C archive seed $seed: rc $? $(tail -1 $out/$C.archive.s$seed.t0.log | cut -c1-600)"
done
CHANGE=/root/repo/build/archive_check SEEDS=2 TRACED=0 bash tools/chip_calls/pr53_cells.sh p53c6 5300000080 serve-olmoe-chat-closed32
exit 0
