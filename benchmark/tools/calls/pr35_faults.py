"""PR 35: does the runner's logits check see the faults this configuration's
check and seeded weights were chosen to expose?  The check of
``serve_ragged.py`` (1,024 + 2 prompt tokens in two chunks, then 8 decode
steps, against the float32 reference) on the cell's engine, a line a
variant:

``clean``: the program as it is.  ``tail_zeroed``: a batch with a tile
segment reads zeros for every slot's tail (a tail lost at a chunk boundary;
decode steps read the true one).  ``silu_left``: SiLU after the taps, as the
Gated DeltaNet convolution has it.  ``b_c_swapped``: ``in_proj``'s first two
blocks exchanged (``C * u`` into the taps, ``B *`` out).  ``bias_dropped``:
the router selects by the score alone.

    python3 benchmark/tools/calls/pr35_faults.py [NAME=value ...] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/lfm2_moe.py``
for this process (``BIAS_STD``, ``EXPERT_DOWN``, ``EMBED_STD``: how the values in that
file were chosen) or ``ONLY=clean,tail_zeroed``.  With ``ONLY=clean`` and many
seeds it is the reading of the gap's spread.  Exits 1 unless ``clean`` is
under ``LOGIT_TOL`` and every fault over it.
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.lib import device, spec                  # noqa: E402
from benchmark.runners import serve_ragged              # noqa: E402
from benchmark.tools.calls.pr35_interleaved import CELL, cell_engine  # noqa: E402


def _tail_zeroed(real):
    def conv(u, w, pool, batch, activation=None):
        if u.shape[0] > batch["state_slot"].shape[0]:   # a tile segment
            out, _ = real(u, w, jnp.zeros_like(pool), batch, activation)
            return out, real(u, w, pool, batch, activation)[1]
        return real(u, w, pool, batch, activation)
    return conv


def _silu_left(real):
    return lambda u, w, pool, batch, activation=None: real(u, w, pool, batch)


def _b_c_swapped(real):
    def qmm(x, w, dt):
        if w.shape[1] == 3 * w.shape[0]:                # in_proj: B | C | u
            b, c, u = jnp.split(w, 3, axis=1)
            w = jnp.concatenate([c, b, u], axis=1)
        return real(x, w, dt)
    return qmm


def _bias_dropped(real):
    return lambda logits, bias, *a, **k: real(logits, jnp.zeros_like(bias),
                                              *a, **k)


def main(argv) -> int:
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_lfm2 as model_mod
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
        f"{n} {getattr(family, n)}" for n in ("BIAS_STD", "EXPERT_DOWN",
                                              "EMBED_STD")),
        flush=True)
    variants = (
        ("clean", None, None, None),
        ("tail_zeroed", model_mod, "_causal_conv", _tail_zeroed),
        ("silu_left", model_mod, "_causal_conv", _silu_left),
        ("b_c_swapped", model_mod, "qmm", _b_c_swapped),
        ("bias_dropped", grouped_gemm, "sigmoid_bias_topk_routing",
         _bias_dropped))
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [3500000091]:
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
            if name == "clean":
                clean.append(gap)
            print(f"seed {seed} {name}: gap {gap:.5f} against {tol}: "
                  f"{'as expected' if seen else 'NOT AS EXPECTED'}",
                  flush=True)
    if len(clean) > 1:
        mean, std = float(np.mean(clean)), float(np.std(clean, ddof=1))
        print(f"clean over {len(clean)} seeds: mean {mean:.5f} std "
              f"{std:.5f} max {max(clean):.5f}; mean + 4 std "
              f"{mean + 4 * std:.5f} against {tol}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
