"""PR 31, chip call 2: where does the logits gap of call 1 come from (0.011,
0.039 and 0.069 on three seeds of the cell's check; 0.0059 / 0.0395 for the
two interleaved requests of ``chip_smoke.py``'s three-layer phase)?

1. the two latent self-test cases (each kernel against its XLA composition
   on synthetic rows at the published widths);
2. the runner's check (1,536 prompt tokens in two chunks + 8 decoded) and
   the four-request interleaved check, with the largest gap of every
   compared ROW, on variants of the cell's configuration: ``f32`` (a
   float32 engine under ``default_matmul_precision("highest")`` at depth 3:
   the implementation without bf16) and ``bf16_d13`` (the cell's own).

    python3 benchmark/tools/calls/pr31_probe.py <seed> [<seed> ...]
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)
sys.path.insert(0, os.path.join(_CHECKOUT, "tools"))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from benchmark.tools.calls.pr31_interleaved import CELL, NEW, PROMPTS  # noqa: E402
from benchmark.tools.interleaved_check import serve_and_compare  # noqa: E402


def _engine(cfg, family, params, dtype):
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    sv = cfg["serve"]
    model = family.serve_model(cfg, int(sv["block_size"]))
    model.config.dtype = dtype
    return InferenceEngineV2(
        model, params, RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": sv["token_budget"],
                "max_ragged_sequence_count":
                    sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"],
                         "num_blocks": 400}}))


def _rows(engine, reference, family, cfg, seed, n_prompt=1536, n_decode=8):
    """``serve_ragged._check_logits`` with the gap of every row."""
    uid = 1 << 40
    ids = np.random.default_rng([seed, 99]).integers(
        0, int(cfg["vocab_size"]), size=(n_prompt + n_decode,))
    got = [np.asarray(engine.put([uid], [ids[:n_prompt].tolist()])[uid],
                      np.float32)]
    for t in ids[n_prompt:]:
        row = engine.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    engine.flush([uid])
    got = np.stack(got)
    want = reference.logits_at(
        family.reference_params(engine.params), ids, cfg,
        rows=list(range(n_prompt - 1, n_prompt + n_decode)))
    return [round(float(g), 5) for g in
            np.max(np.abs(got - want), axis=1) / np.max(np.abs(want))]


def main(seeds) -> int:
    from kernel_selftest import latent_prefill_case, latent_read_case

    bench = spec.benchmark_spec()
    base = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    print("latent_decode_walk", latent_read_case(3e-2, layers=4), flush=True)
    got, want = latent_prefill_case()
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    print("latent_prefill max_err", float(jnp.max(err)), "max |want|",
          float(jnp.max(jnp.abs(want.astype(jnp.float32)))),
          "rows over 0.03:", int(jnp.sum(jnp.max(err, axis=(1, 2)) > 0.03)),
          "of", got.shape[0], flush=True)
    family = spec.module("families", base["family"])
    reference = spec.module("reference", family.REFERENCE)
    for seed in seeds:
        for name, layers, dtype in (("f32", 3, jnp.float32),
                                    ("bf16_d13", 13, jnp.bfloat16)):
            cfg = dict(base, num_hidden_layers=layers)
            params = serve_ragged.make_params(family, cfg, seed)
            if dtype == jnp.float32:
                params = jax.tree.map(lambda a: a.astype(jnp.float32),
                                      params)
            with jax.default_matmul_precision(
                    "highest" if dtype == jnp.float32 else "default"):
                engine = _engine(cfg, family, params, dtype)
                rows = _rows(engine, reference, family, cfg, seed)
                rng = np.random.default_rng([seed, 31])
                prompts = [rng.integers(0, int(cfg["vocab_size"]),
                                        size=(n,)).tolist() for n in PROMPTS]
                out = serve_and_compare(
                    engine, reference, family.reference_params(
                        engine.params), cfg, prompts, NEW)
            print(f"seed {seed} {name}: check rows {rows}; interleaved "
                  f"{[round(g, 5) for g in out['gaps']]}", flush=True)
            del engine, params
            gc.collect()    # the step programs' closures hold the engine
    return 0


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [3100000021]))
