"""PR 55, one-off for the chip: where a cell's warm set-up goes, by the host's clock, WITHOUT a profiler (under
cProfile the change and the parent read level; without it the change read 2-3 s over).  Run from a checkout's root:
``benchmark.run.run_cell`` with a 2 s window, ``_check_logits`` / ``_shape_ladder`` and the engine's ``_get_step``
(the program's trace, lowering and cache read) and ``launch`` timed around.

    python3 /root/repo/tools/chip_calls/pr55_setup_phases.py <cell> <seed>"""
import collections
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.getcwd())
from benchmark import run                                   # noqa: E402
from benchmark.runners import serve_ragged                  # noqa: E402
from deepspeed_tpu.inference.v2 import engine_v2            # noqa: E402

marks, spent = [("imports", time.perf_counter() - T0)], collections.Counter()


def timed(owner, name, label, each=False):
    real = getattr(owner, name)

    def wrapper(*a, **kw):
        t = time.perf_counter()
        try:
            return real(*a, **kw)
        finally:
            dt = time.perf_counter() - t
            spent[label] += dt
            if each:
                marks.append((label, round(dt, 3)))
    setattr(owner, name, wrapper)


timed(serve_ragged, "make_params", "make_params", each=True)
timed(serve_ragged, "_check_logits", "_check_logits", each=True)
timed(serve_ragged, "_shape_ladder", "_shape_ladder", each=True)
timed(engine_v2.InferenceEngineV2, "_get_step", "engine._get_step")
timed(engine_v2.InferenceEngineV2, "launch", "engine.launch")
timed(engine_v2.InferenceEngineV2, "decode_step", "engine.decode_step")
out = run.run_cell(sys.argv[1], int(sys.argv[2]), 2.0, False)
print(json.dumps({"cwd": os.getcwd(), "setup_s": out["metrics"]["setup_s"]["value"], "each": marks,
                  "summed": {k: round(v, 3) for k, v in spent.items()}}), flush=True)
