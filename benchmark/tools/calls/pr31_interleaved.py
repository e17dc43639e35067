"""PR 31: four requests of different lengths served TOGETHER through the
scheduler on the Moonlight cell's own engine (published widths, the cell's
pool, budget and 64 slots), each one's logits against its own float32
expanded-form reference forward (``tools/interleaved_check.py``).  The
longest prompt is over 2,048 tokens, so it takes three chunks through the
expanded path while the others decode beside it through the absorbed one;
each generates 16 tokens.  The accepted ``_check_logits`` feeds one
sequence: a latent row written to another sequence's block shows only here.

    python3 benchmark/tools/calls/pr31_interleaved.py <seed> [<seed> ...]

Prints one line a seed and exits 1 when any gap is over the runner's
``LOGIT_TOL``.
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from benchmark.tools.interleaved_check import serve_and_compare  # noqa: E402

CELL = "serve-moonlight-longdoc-closed64"
PROMPTS, NEW = (2304, 1100, 600, 300), (16, 16, 16, 16)


def cell_engine(cfg, family, seed: int):
    """The cell's engine on seeded weights (shared with pr31_faults.py)."""
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
        rng = np.random.default_rng([seed, 31])
        prompts = [rng.integers(0, int(cfg["vocab_size"]), size=(n,)).tolist()
                   for n in PROMPTS]
        out = serve_and_compare(engine, reference,
                                family.reference_params(engine.params), cfg,
                                prompts, NEW)
        worst = max(worst, *out["gaps"])
        print(f"seed {seed}: prompts {PROMPTS} gaps "
              f"{[round(g, 5) for g in out['gaps']]} rows {out['rows']} "
              f"ticks {out['ticks']} blocks free after "
              f"{engine.state_manager.free_blocks}", flush=True)
        del engine
        gc.collect()        # the step programs' closures hold the engine
    ok = worst <= serve_ragged.LOGIT_TOL
    print(f"interleaved: worst gap {worst:.5f} against "
          f"{serve_ragged.LOGIT_TOL}: {'ok' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3100000001]))
