"""PR 29: four requests of different lengths served TOGETHER through the
scheduler on the Qwen3-Next cell's own engine (published widths, the cell's
pools and budget), each one's logits against its own float32 reference
forward (``tools/interleaved_check.py``).  The longest prompt is over 2,048
tokens, so it takes three chunks while the others decode beside it; each
generates 16 tokens.  The accepted ``_check_logits`` feeds one sequence: a
state slot mixed up between sequences shows only here.

    python3 benchmark/tools/calls/pr29_interleaved.py <seed> [<seed> ...]

Prints one line a seed and exits 1 when any gap is over the runner's
``LOGIT_TOL``.
"""

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from benchmark.tools.interleaved_check import serve_and_compare  # noqa: E402

CELL = "serve-qwen3next-longchat-closed32"
PROMPTS, NEW = (2304, 1100, 600, 300), (16, 16, 16, 16)


def main(seeds) -> int:
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    sv = cfg["serve"]
    worst = 0.0
    for seed in seeds:
        engine = InferenceEngineV2(
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
        rng = np.random.default_rng([seed, 29])
        prompts = [rng.integers(0, int(cfg["vocab_size"]), size=(n,)).tolist()
                   for n in PROMPTS]
        out = serve_and_compare(engine, reference,
                                family.reference_params(engine.params), cfg,
                                prompts, NEW)
        worst = max(worst, *out["gaps"])
        print(f"seed {seed}: prompts {PROMPTS} gaps "
              f"{[round(g, 5) for g in out['gaps']]} rows {out['rows']} "
              f"ticks {out['ticks']} slots held after "
              f"{engine.state_manager.state_pool.held}", flush=True)
        del engine
    ok = worst <= serve_ragged.LOGIT_TOL
    print(f"interleaved: worst gap {worst:.5f} against "
          f"{serve_ragged.LOGIT_TOL}: {'ok' if ok else 'FAILED'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [2900000001]))
