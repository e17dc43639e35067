"""PR 29, chip call 2: where does the logits gap of the first call (0.07-0.11
of the largest logit at the cell's size, 0.014-0.016 in ``chip_smoke.py``'s
one-period phase with 16 experts held) come from?  The runner's own check
(two prefill chunks + 8 decode steps against the float32 token-by-token
reference) on variants of the cell's configuration, one line each:

    [PROBE=all] python3 benchmark/tools/calls/pr29_precision_probe.py <seed> ...

(without ``PROBE=all`` the ``cell`` line only, a seed after another;
``PROBE=fault`` adds the same check with the carried state dropped at every
tile: what a fault reads against the limit).

``cell``: as the cell runs.  ``depth4``: one period less.  ``held16``: 16 of
the 512 experts held (the routed part nearly gone).  ``f32``: a float32
engine under ``default_matmul_precision("highest")`` at depth 8 with 16
experts held (what fits): the implementation without bf16.  ``router_x4``
.. : the router's seeded kernel N(0, s^2 / fan_in) instead of N(0, 1 /
fan_in).
"""

import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402

CELL = "serve-qwen3next-longchat-closed32"


def main(seed: int) -> int:
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    bench = spec.benchmark_spec()
    base = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    family = spec.module("families", base["family"])
    reference = spec.module("reference", family.REFERENCE)
    sv = base["serve"]
    if os.environ.get("DT_SHIFT"):
        family.DT_SHIFT = float(os.environ["DT_SHIFT"])
        print(f"DT_SHIFT {family.DT_SHIFT}", flush=True)
    real_std = family.init_std

    def run(name, over, router_scale=1.0, f32=False):
        cfg = dict(base, **over)

        def init_std(path, shape):
            std = real_std(path, shape)
            return std * router_scale if "wg" in path else std

        family.init_std = init_std
        try:
            params = serve_ragged.make_params(family, cfg, seed)
        finally:
            family.init_std = real_std
        model = family.serve_model(cfg, int(sv["block_size"]))
        if f32:
            params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
            model._model.config.dtype = jnp.float32
        engine = InferenceEngineV2(
            model, params, RaggedInferenceEngineConfig.from_dict({
                "state_manager": {
                    "max_ragged_batch_size": sv["token_budget"],
                    "max_ragged_sequence_count":
                        sv["max_ragged_sequence_count"],
                    "max_context": sv["max_context"]},
                "kv_cache": {"block_size": sv["block_size"],
                             "num_blocks": sv["kv_pool_blocks"]}}))
        with jax.default_matmul_precision("highest" if f32 else "default"):
            gap = serve_ragged._check_logits(
                engine, reference, family, cfg, seed,
                int(sv["check_prompt_tokens"]),
                int(sv["check_decode_tokens"]))
        print(f"seed {seed} {name}: logits gap {gap:.5f}", flush=True)
        del engine, params

    run("cell", {})
    if os.environ.get("PROBE") == "fault":
        # the second readings beside the limit: the same check with a fault
        # in what is carried (each at the seeded decay as it is and at a
        # slower one, DT_SHIFT from the environment)
        from deepspeed_tpu.inference.v2.model_implementations import (
            ragged_qwen3_next as rq)

        real_chunk, real_step = rq.gdn_chunk, rq.gdn_step

        def chunk_forgets(pool, q, k, v, g, beta, slot, reset, tile,
                          interpret=None):
            # every prompt chunk starts from a zeroed state
            first = jnp.arange(reset.shape[0]) == 0
            return real_chunk(pool, q, k, v, g, beta, slot, reset | first,
                              tile, interpret=interpret)

        def step_forgets(pool, q, k, v, g, beta, slots, reset,
                         interpret=None):
            # a decode step is handed a zeroed state
            return real_step(pool, q, k, v, g, beta, slots,
                             jnp.ones_like(reset), interpret=interpret)

        for name, patch in (("state dropped at the chunk boundary",
                             {"gdn_chunk": chunk_forgets}),
                            ("decode steps handed a zeroed state",
                             {"gdn_step": step_forgets})):
            for attr, fn in patch.items():
                setattr(rq, attr, fn)
            try:
                run(name, {})
            finally:
                rq.gdn_chunk, rq.gdn_step = real_chunk, real_step
    if os.environ.get("PROBE") == "all":
        run("depth4", {"num_hidden_layers": 4})
        run("held16", {"num_experts": 16})
        run("f32 held16", {"num_experts": 16}, f32=True)
        for scale in (2.0, 4.0, 8.0):
            run(f"router_x{scale:g}", {}, router_scale=scale)
    return 0


if __name__ == "__main__":
    for arg in sys.argv[1:] or ["2900000031"]:
        main(int(arg))
