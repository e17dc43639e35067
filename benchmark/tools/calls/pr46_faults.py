"""PR 46: does the runner's logits check see the faults a model with
state-space layers can have?  The check of ``serve_ragged.py`` (1,536 prompt
tokens in two chunks of 1,024 and 512, then 8 decode steps, against the
float32 reference) on the cell's engine, a line a variant.  Before each
check another sequence is served and flushed, so the slot the check takes
is not a fresh one: it holds that sequence's state.

``clean``: the program as it is.  ``carry_dropped``: every prompt chunk's
scan starts from a zeroed state (the carry across the 1,024 boundary is
lost; the convolution's tail is kept).  ``slot_not_zeroed``: a sequence
whose chunk starts at position 0 keeps what its slot held (``reset``
ignored in both kernels).  ``conv_bias_dropped``: the convolution without
its bias.  ``inner_norms_dropped``: no RMSNorm on ``dt_r``, ``B``, ``C``
(plain Mamba under Jamba's name).  ``d_skip_dropped``: ``y`` without ``D
x``.  ``rotary_applied``: the attention layers rotate q and k (theta
10,000) where the model has no positions.

One reading that counts for nothing: ``bf16_state``: the scan state rounded
to bf16 wherever it is stored (after every call of either kernel: what a
bf16 slot pool would hold; inside a chunk the state stays in the kernel's
registers in float32 either way).  It is no fault of the mathematics and
the published cache is bf16; it is printed with what it reads.

And one control that is no fault of the program: ``reference_low_precision``
is the unchanged engine against the float32 reference computed on weights cut
to the nearest precision below the bf16 the configuration states (every
matrix rounded to float8_e4m3's 3 mantissa bits, bf16's exponent kept),
through the runner's own comparison: what a computation below bf16 reads,
which has to be over the limit too.

    python3 benchmark/tools/calls/pr46_faults.py [NAME=value ...] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/jamba.py`` for
this process (``DT_SHIFT``, ``D_SCALE``, ``MAMBA_OUT``, ...: how the values
in that file were chosen) or ``ONLY=clean,carry_dropped``.  With
``ONLY=clean`` and many seeds it is the reading of the gap's spread.
Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it.  ``fault(name)`` is also what
``tests/unit/test_ragged_jamba.py`` applies at tiny sizes on the CPU.
"""

import contextlib
import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

CELL = "serve-jamba2-reason-closed256"
FAULTS = ("carry_dropped", "slot_not_zeroed", "conv_bias_dropped",
          "inner_norms_dropped", "d_skip_dropped", "rotary_applied")
#: no fault of the mathematics: printed, counted for nothing (module doc)
READINGS = ("bf16_state",)
#: no fault: the reference below the stated precision (module doc)
CONTROLS = ("reference_low_precision",)
SEEDING = ("EMBED_STD", "DT_SHIFT", "DT_SCALE", "D_SCALE", "CONV_BIAS_STD",
           "MAMBA_OUT", "ATTN_OUT")


@contextlib.contextmanager
def fault(name: str):
    """The program with one fault in it, for engines built and run inside
    the block."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_jamba as model_mod
    from deepspeed_tpu.inference.v2.modules.attention import _rotary

    cls = model_mod.RaggedJamba
    real_step, real_chunk = model_mod.ssm_step, model_mod.ssm_chunk
    patches = []
    if name == "carry_dropped":
        def chunk(pool, dt, dtx, b, c, a, slot, reset, tile, **k):
            first = jnp.concatenate([jnp.ones((1,), bool),
                                     slot[1:] != slot[:-1]])
            return real_chunk(pool, dt, dtx, b, c, a, slot, first, tile, **k)
        patches.append((model_mod, "ssm_chunk", chunk))
    elif name == "slot_not_zeroed":
        never = lambda reset: jnp.zeros_like(reset)
        patches += [
            (model_mod, "ssm_step", lambda p, dt, dtx, b, c, a, slot, reset,
             **k: real_step(p, dt, dtx, b, c, a, slot, never(reset), **k)),
            (model_mod, "ssm_chunk", lambda p, dt, dtx, b, c, a, slot, reset,
             tile, **k: real_chunk(p, dt, dtx, b, c, a, slot, never(reset),
                                   tile, **k))]
    elif name == "bf16_state":
        stored = lambda out: (out[0], out[1].astype(jnp.bfloat16).astype(
            out[1].dtype))
        patches += [
            (model_mod, "ssm_step", lambda *a, **k: stored(
                real_step(*a, **k))),
            (model_mod, "ssm_chunk", lambda *a, **k: stored(
                real_chunk(*a, **k)))]
    elif name in ("conv_bias_dropped", "d_skip_dropped"):
        real_mamba = cls._mamba

        def mamba(self, lp, *a, **k):
            mb = dict(lp["mamba"])
            if name == "d_skip_dropped":
                mb["D"] = jnp.zeros_like(mb["D"])
            else:
                mb["conv1d"] = {"kernel": mb["conv1d"]["kernel"]}
            return real_mamba(self, {**lp, "mamba": mb}, *a, **k)
        patches.append((cls, "_mamba", mamba))
    elif name == "inner_norms_dropped":
        # the norms over the hidden size are the layers'; the narrower ones
        # (dt_rank, d_state) Jamba's inner norms
        real_norm, real_mamba, at = model_mod._rms_norm, cls._mamba, {}

        def mamba(self, *a, **k):
            at["hidden"] = self.config.hidden_size
            return real_mamba(self, *a, **k)

        patches += [
            (cls, "_mamba", mamba),
            (model_mod, "_rms_norm", lambda x, scale, eps:
             x if scale.shape[-1] != at.get("hidden", scale.shape[-1])
             else real_norm(x, scale, eps))]
    elif name == "rotary_applied":
        real_block = model_mod.ragged_attention_block

        def block(lp, xa, cache, batch, bs, cfg, h, hkv, d, cos, sin, **k):
            cos, sin = _rotary(batch["token_pos"], d, 10000.0)
            return real_block(lp, xa, cache, batch, bs, cfg, h, hkv, d, cos,
                              sin, **k)
        patches.append((model_mod, "ragged_attention_block", block))
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


def dirty_slot(engine, vocab: int, tokens: int, seed: int) -> None:
    """Serve and flush one sequence, so that the slot (and the blocks) the
    next sequence takes hold its state."""
    ids = np.random.default_rng([seed, 46]).integers(0, vocab, (tokens,))
    engine.put([77], [ids.tolist()])
    engine.decode_step([77], [int(ids[0])])
    engine.flush([77])


def main(argv) -> int:
    from benchmark.lib import device, spec
    from benchmark.runners import serve_ragged
    from benchmark.tools.calls.pr39_faults import (_LowPrecisionReference,
                                                   cell_engine)

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
    print("seeding: " + ", ".join(f"{k} {getattr(family, k)}"
                                  for k in SEEDING), flush=True)
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [4600000046]:
        for name in ("clean",) + FAULTS + READINGS + CONTROLS:
            if only and name not in only:
                continue
            control = name in CONTROLS
            with fault("clean" if control else name):
                engine = cell_engine(cfg, family, seed)
                dirty_slot(engine, int(cfg["vocab_size"]),
                           int(sv["token_budget"]), seed)
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
