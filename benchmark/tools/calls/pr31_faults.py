"""PR 31: does the runner's logits check see the faults this configuration's
seeded weights were chosen to expose?  The check of ``serve_ragged.py``
(1,536 prompt tokens in two chunks through the expanded path, 8 tokens
through the absorbed one, against the float32 expanded-form reference) on
the cell's engine, a line a variant:

``clean``: the program as it is.  ``no_bias``: the router selects by the
score alone (the selection bias dropped).  ``bias_in_weights``: the chosen
experts are weighed by score + bias.  ``own_chunk_only``: a prompt chunk's
expanded context loses every block before the chunk's first (the second
chunk then sees nothing of what the first cached).

    python3 benchmark/tools/calls/pr31_faults.py [NAME=value ...] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/moonlight.py``
for this process (``BIAS_MEAN``, ``BIAS_STD``, ``EXPERT_DOWN``, ``Q_SCALE``:
how the values in that file were chosen) or ``ONLY=clean,own_chunk_only``.
Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault over it.
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from benchmark.tools.calls.pr31_interleaved import CELL, cell_engine  # noqa: E402


def _no_bias(real):
    return lambda logits, bias, *a, **k: real(logits, jnp.zeros_like(bias),
                                              *a, **k)


def _bias_in_weights(_real):
    def route(logits, bias, k, renormalize=True, scale=1.0):
        sb = jax.nn.sigmoid(logits.astype(jnp.float32)) \
            + bias.astype(jnp.float32)
        topw, topi = jax.lax.top_k(sb, k)
        if renormalize:
            topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-20)
        return topi.astype(jnp.int32), topw * scale
    return route


def _own_chunk_only(real):
    def expand(pool, w_kvb, tables, slot, pos, *, block_size, tile_q, **kw):
        kv, plan = real(pool, w_kvb, tables, slot, pos,
                        block_size=block_size, tile_q=tile_q, **kw)
        nt = pos.shape[0] // tile_q
        first = jnp.where(pos >= 0, pos, 1 << 30).reshape(nt, tile_q).min(1)
        owner = plan[2]
        first = first[owner]            # the chunk's first position
        j = jnp.arange(kv.shape[0] // nt)
        keep = (j[None, :] >= first[:, None] // block_size).reshape(-1)
        return jnp.where(keep[:, None, None], kv, 0), plan
    return expand


def main(argv) -> int:
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_deepseek_v3 as model_mod
    from deepspeed_tpu.ops import grouped_gemm

    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    sv = cfg["serve"]
    only, seeds = None, []
    for arg in argv:
        name, _, value = arg.partition("=")
        if name == "ONLY":
            only = value.split(",")
        elif value:
            setattr(family, name, float(value))
        else:
            seeds.append(int(arg))
    print("seeding: " + ", ".join(
        f"{n} {getattr(family, n)}" for n in
        ("BIAS_MEAN", "BIAS_STD", "EXPERT_DOWN", "Q_SCALE")), flush=True)
    variants = (
        ("clean", None, None, None),
        ("no_bias", grouped_gemm, "sigmoid_bias_topk_routing", _no_bias),
        ("bias_in_weights", grouped_gemm, "sigmoid_bias_topk_routing",
         _bias_in_weights),
        ("own_chunk_only", model_mod, "latent_expand", _own_chunk_only))
    tol, bad = serve_ragged.LOGIT_TOL, 0
    for seed in seeds or [3100000011]:
        for name, mod, attr, make in variants:
            if only and name not in only:
                continue
            real = getattr(mod, attr) if mod else None
            if mod:
                setattr(mod, attr, make(real))
            try:
                engine = cell_engine(cfg, family, seed)
                gap = serve_ragged._check_logits(
                    engine, reference, family, cfg, seed,
                    int(sv["check_prompt_tokens"]),
                    int(sv["check_decode_tokens"]))
            finally:
                if mod:
                    setattr(mod, attr, real)
            del engine
            gc.collect()    # the step programs' closures hold the engine
            seen = (gap <= tol) if name == "clean" else (gap > tol)
            bad += not seen
            print(f"seed {seed} {name}: gap {gap:.5f} against {tol}: "
                  f"{'as expected' if seen else 'NOT AS EXPECTED'}",
                  flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
