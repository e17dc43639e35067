"""PR 43: does the runner's logits check see the faults a looped model can
have?  The check of ``serve_ragged.py`` (512 prompt tokens in one chunk, then
8 decode steps, against the float32 reference) on the cell's engine, a line a
variant:

``clean``: the program as it is.  ``pass_reads_pass0``: pass 1 reads pass
0's cache (its block tables are not moved to its own part of the pools; its
writes are): the off-by-one a cache indexed ``layer + pass x layers`` invites.
``pass_norm_dropped``: the final norm left out BETWEEN the passes (the last
pass keeps it, so the head still reads a normed state).  ``three_passes``:
the loop makes one trip too few.  ``post_norms_dropped``: the norm after each
branch left out in every layer (a Llama block under this model's name).
``bf16_stream``: the hidden state between the layers kept in bf16 (rounded
at each of a token's 384 residual sums), which is no fault of the
mathematics: it is printed with what it reads and counts for nothing (why
the stream is float32: ``PERF.md`` section 6, PR 43).  ``unrolled``: the
passes written out in the program's text (a Python loop for the
``fori_loop``), the same arithmetic: what ``pr43_loop_vs_unrolled.py``
measures the loop against and a CPU test compares it with.

And one control that is no fault of the program: ``reference_low_precision``
is the unchanged engine against the float32 reference computed on weights cut
to the nearest precision below the bf16 the configuration states (every
matrix rounded to float8_e4m3's 3 mantissa bits, bf16's exponent kept),
through the runner's own comparison: what a computation below bf16 reads,
which has to be over the limit too.

    python3 benchmark/tools/calls/pr43_faults.py [NAME=value ...] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/ouro.py`` for
this process (``POST_NORM_STD``: how the value in that file was chosen) or
``ONLY=clean,three_passes``.  With ``ONLY=clean`` and many seeds it is the
reading of the gap's spread.
Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it.  ``fault(name)`` is also what
``tests/unit/test_ragged_ouro.py`` applies at tiny sizes on the CPU.
"""

import contextlib
import functools
import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

# the control's reference on cut weights and the cell's engine on seeded
# weights are PR 39's, which know no family
from benchmark.tools.calls.pr39_faults import (         # noqa: E402
    _LowPrecisionReference, cell_engine)

CELL = "serve-ouro-reason-closed8"
FAULTS = ("pass_reads_pass0", "pass_norm_dropped", "three_passes",
          "post_norms_dropped")
#: no fault of the mathematics: printed, counted for nothing (module doc)
READINGS = ("bf16_stream", "unrolled")
#: no fault: the reference below the stated precision (module doc)
CONTROLS = ("reference_low_precision",)


@contextlib.contextmanager
def fault(name: str):
    """The program with one fault in it, for engines built and run inside
    the block."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_ouro as model_mod

    cls = model_mod.RaggedOuro
    patches = []
    if name == "pass_reads_pass0":
        real_view = cls._pass_view

        def view(self, batch, t, pass_rows):
            out = real_view(self, batch, t, pass_rows)
            read = real_view(self, batch, jnp.where(t == 1, 0, t), pass_rows)
            return {**out, "block_tables": read["block_tables"]}
        patches.append((cls, "_pass_view", view))
    elif name in ("pass_norm_dropped", "post_norms_dropped"):
        # a pass calls the norm 4 times a layer (input, after attention,
        # before the MLP, after it), then once more: the final norm
        real_norm, real_pass, at = model_mod._rms_norm, cls._one_pass, {}

        def one_pass(self, params, t, *a, **k):
            at.update(t=t, n=0, model=self)
            return real_pass(self, params, t, *a, **k)

        def norm(x, scale, eps):
            i, cfg = at["n"], at["model"].config
            at["n"] += 1
            final = i == 4 * cfg.num_hidden_layers
            if name == "post_norms_dropped" and not final and i % 2:
                return x
            if name == "pass_norm_dropped" and final:
                return jnp.where(at["t"] == cfg.total_ut_steps - 1,
                                 real_norm(x, scale, eps), x)
            return real_norm(x, scale, eps)
        patches += [(model_mod, "_rms_norm", norm), (cls, "_one_pass",
                                                     one_pass)]
    elif name == "three_passes":
        real_loop = model_mod.fori_loop
        patches.append((model_mod, "fori_loop", lambda lo, hi, body, init:
                        real_loop(lo, hi - 1, body, init)))
    elif name == "bf16_stream":
        patches.append((cls, "stream", jnp.bfloat16))
    elif name == "unrolled":
        patches.append((model_mod, "fori_loop", lambda lo, hi, body, init:
                        functools.reduce(lambda carry, t: body(t, carry),
                                         range(lo, hi), init)))
    elif name != "clean":
        raise KeyError(name)
    olds = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    for mod, attr, new in patches:
        setattr(mod, attr, new)
    try:
        yield
    finally:
        for mod, attr, old in olds:
            setattr(mod, attr, old)


def main(argv) -> int:
    from benchmark.lib import device, spec
    from benchmark.runners import serve_ragged

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
    print(f"seeding: POST_NORM_STD {family.POST_NORM_STD}", flush=True)
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [4300000043]:
        for name in ("clean",) + FAULTS + READINGS + CONTROLS:
            if only and name not in only:
                continue
            control = name in CONTROLS
            with fault("clean" if control else name):
                engine = cell_engine(cfg, family, seed)
                gap = serve_ragged._check_logits(
                    engine, reference,
                    _LowPrecisionReference(family) if control else family,
                    cfg, seed, int(sv["check_prompt_tokens"]),
                    int(sv["check_decode_tokens"]))
            del engine
            gc.collect()    # the step programs' closures hold the engine
            seen = (gap <= tol) if name == "clean" else (gap > tol)
            if name in READINGS:
                verdict = "a reading"
            else:
                bad += not seen
                verdict = "as expected" if seen else "NOT AS EXPECTED"
            if name == "clean":
                clean.append(gap)
            print(f"seed {seed} {name}: gap {gap:.5f} against {tol}: "
                  f"{verdict}", flush=True)
    if len(clean) > 1:
        mean, std = float(np.mean(clean)), float(np.std(clean, ddof=1))
        print(f"clean over {len(clean)} seeds: mean {mean:.5f} std "
              f"{std:.5f} max {max(clean):.5f}; mean + 4 std "
              f"{mean + 4 * std:.5f} against {tol}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
