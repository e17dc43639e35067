"""PR 35: four requests of different lengths served TOGETHER through the
scheduler on the LFM2 cell's own engine (published widths, the cell's pool,
budget and 128 slots), each one's logits against its own float32 reference
forward (``tools/interleaved_logits.py``).  One prompt is over 2,048 tokens,
so it takes three chunks while the others decode beside it; one is 1,025
tokens, so its second chunk is ONE row, which reads both rows of the tail
its first chunk left in the slot; each generates 16 tokens.  The accepted
``_check_logits`` feeds one sequence: a tail written to another sequence's
slot shows only here.

    python3 benchmark/tools/calls/pr35_interleaved.py <seed> [<seed> ...]

Prints one line a seed and exits 1 when any gap is over the runner's
``LOGIT_TOL``.
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for _path in (_CHECKOUT, os.path.join(_CHECKOUT, "tools")):
    sys.path.insert(0, _path)

import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from interleaved_logits import serve_and_compare        # noqa: E402

CELL = "serve-lfm2-agent-closed128"
PROMPTS, NEW = (2304, 1025, 600, 300), (16, 16, 16, 16)


def cell_engine(cfg, family, seed: int):
    """The cell's engine on seeded weights (shared with pr35_faults.py)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    sv = cfg["serve"]
    return InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])),
        serve_ragged.make_params(family, cfg, seed),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": sv["token_budget"],
                "max_ragged_sequence_count":
                    sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"],
                         "num_blocks": sv["kv_pool_blocks"]}}))


def main(seeds) -> int:
    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    worst = 0.0
    for seed in seeds:
        engine = cell_engine(cfg, family, seed)
        rng = np.random.default_rng([seed, 35])
        prompts = [rng.integers(0, int(cfg["vocab_size"]), size=(n,)).tolist()
                   for n in PROMPTS]
        out = serve_and_compare(engine, reference,
                                family.reference_params(engine.params), cfg,
                                prompts, NEW)
        worst = max(worst, *out["gaps"])
        pool = engine.state_manager.state_pool
        print(f"seed {seed}: prompts {PROMPTS} gaps "
              f"{[round(g, 5) for g in out['gaps']]} rows {out['rows']} "
              f"ticks {out['ticks']} blocks free after "
              f"{engine.state_manager.free_blocks} state slots held after "
              f"{pool.held}", flush=True)
        del engine
        gc.collect()        # the step programs' closures hold the engine
    ok = worst <= serve_ragged.LOGIT_TOL
    print(f"interleaved: worst gap {worst:.5f} against "
          f"{serve_ragged.LOGIT_TOL}: {'ok' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3500000093]))
