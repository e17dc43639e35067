"""PR 39: does the runner's logits check see the faults this configuration's
check and seeded weights were chosen to expose?  The check of
``serve_ragged.py`` (6,144 prompt tokens in six chunks, so that the checked
rows sit 2,048 past the window and the window group has released 16 blocks,
then 8 decode steps, against the float32 reference) on the cell's engine, a
line a variant:

``clean``: the program as it is.  ``window_dropped``: no band mask in the
window layers (their table still holds the band alone: what lies below it
reads the trash block).  ``window_in_global``: the band mask in the global
layer too.  ``rope_in_global``: q and k rotated in the global layer.
``rope_dropped``: not rotated in the window layers.  ``gate_dropped``: the
attention gate's projection zeroed (``sigmoid(0)`` is a constant, which the
post norm undoes: the output ungated).  ``post_norm_dropped``: layer 0's
post-attention norm left out.  ``sqrt_h_dropped``: the embedding not
multiplied by ``sqrt(hidden_size)``.  ``bias_dropped``: the router selects
by the score alone.  ``bias_in_weights``: the experts' weights from ``score
+ bias``.  ``released_block_read``: the lowest live entry of every window
table that has released something names ANOTHER block that was written
since (the check feeds one sequence, so the block is that sequence's
newest): what a table left pointing at a released and reused block reads,
up to 128 of a row's 4,096 visible keys in each window layer.
``routed_dropped``: the routed experts' down projections zeroed (the shared
expert alone): how much of the routed experts the check sees at the seeded
``EXPERT_DOWN``.

And one control that is no fault of the program: ``reference_low_precision``
is the unchanged engine against the float32 reference computed on weights cut
to the nearest precision below the bf16 the configuration states (every
matrix rounded to float8_e4m3's 3 mantissa bits, bf16's exponent kept, so no
scale is needed and nothing under- or overflows), through the runner's own
comparison: what a computation below bf16 reads, which has to be over the
limit too.

    python3 benchmark/tools/calls/pr39_faults.py [NAME=value ...] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/afmoe.py`` for
this process (``BIAS_MEAN``, ``BIAS_STD``, ``EXPERT_DOWN``: how the values in
that file were chosen) or ``ONLY=clean,gate_dropped``.  With ``ONLY=clean``
and many seeds it is the reading of the gap's spread.
Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it, but for the three faults of the routed experts
(``BY_SEED``), which are printed with what they read and count for nothing:
59% of tokens use none of the 32 held experts in a layer, so what the 9
checked rows can show of them is a matter of the seed.  At the family's
``EXPERT_DOWN`` 0.075 ``routed_dropped`` read 0.023 and 0.033 on two seeds
and ``bias_in_weights`` 0.023 and 0.159; ``bias_dropped`` (0.014, 0.021)
moves a fifth of the selections, which on a checked row is one held expert
come or gone, the same thing a routing near-tie does to the clean program
(up to 0.017 in 26 seeds), and the maximum norm cannot tell the two apart at
any scale (``PERF.md`` section 6, PR 39).  The CPU tests see all three in
float32.
``fault(name)`` is also what ``tests/unit/test_ragged_afmoe.py`` applies at
tiny sizes on the CPU.
"""

import contextlib
import dataclasses
import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

CELL = "serve-trinity-mixedlen-closed32"
FAULTS = ("window_dropped", "window_in_global", "rope_in_global",
          "rope_dropped", "gate_dropped", "post_norm_dropped",
          "sqrt_h_dropped", "bias_dropped", "bias_in_weights",
          "released_block_read", "routed_dropped")
#: no fault: the reference below the stated precision (module doc)
CONTROLS = ("reference_low_precision",)
#: the faults the check sees on some seeds only (module doc)
BY_SEED = ("bias_dropped", "bias_in_weights", "routed_dropped")


class _ParamsOnTheWayIn:
    """The served model with ``change`` applied to the parameters on their
    way in (the reference reads the engine's own, unchanged)."""

    def __init__(self, model, change):
        self._model, self._change = model, change

    def __getattr__(self, name):
        return getattr(self._model, name)

    def __call__(self, params, *args, **kwargs):
        return self._model(self._change(params), *args, **kwargs)


def _stale_entry(tables):
    """In every row of [S, B] window tables that has trash below its live
    entries, the lowest live entry names the row's newest block."""
    tables = np.array(tables)
    for row in tables:
        live = np.flatnonzero(row)
        if len(live) > 1 and live[0] > 0:
            row[live[0]] = row[live[-1]]
    return tables


@contextlib.contextmanager
def fault(name: str, window=None):
    """The program with one fault in it; yields ``fix(engine)``, to be
    called on an engine built INSIDE the block (the two faults that live in
    an engine's model).  ``window``: what ``window_in_global`` applies."""
    from deepspeed_tpu.inference.v2 import engine_v2
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_afmoe as model_mod
    from deepspeed_tpu.inference.v2.ragged import ragged_wrapper
    from deepspeed_tpu.ops import grouped_gemm

    cls = model_mod.RaggedAfmoe
    patches, fix = [], lambda engine: engine

    def attention(change):
        real = cls._attention

        def wrapped(self, lp, x, cache, batch, cos, sin, window, *rest):
            if change == "rope_in_global" and cos is None:
                cos = model_mod._rotary(batch["token_pos"],
                                        self.config.head_dim,
                                        self.config.rope_theta)[0]
            if change == "rope_dropped" and window is not None:
                cos = None
            return real(self, lp, x, cache, batch, cos, sin, window, *rest)
        return wrapped

    if name == "window_dropped":
        real = model_mod._paged_attention
        patches.append((model_mod, "_paged_attention",
                        lambda *a, window=None, **k: real(*a, window=None,
                                                          **k)))
    elif name == "window_in_global":
        real = model_mod._paged_attention
        wide = window
        patches.append((model_mod, "_paged_attention", lambda *a, window=None,
                        **k: real(*a, window=window or wide, **k)))
    elif name in ("rope_in_global", "rope_dropped"):
        patches.append((cls, "_attention", attention(name)))
    elif name == "gate_dropped":
        def zero_gates(params):
            return jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.zeros_like(a) if [
                    str(getattr(p, "key", p)) for p in path][-3:-1] == [
                        "self_attn", "gate_proj"] else a, params)

        def fix(engine):
            engine.model = _ParamsOnTheWayIn(engine.model, zero_gates)
            return engine
    elif name == "routed_dropped":
        def zero_down(params):
            return jax.tree_util.tree_map_with_path(
                lambda path, a: jnp.zeros_like(a) if str(getattr(
                    path[-1], "key", path[-1])) == "w_down" else a, params)

        def fix(engine):
            engine.model = _ParamsOnTheWayIn(engine.model, zero_down)
            return engine
    elif name == "post_norm_dropped":
        real_norm, real_call, calls = model_mod._rms_norm, cls.__call__, [0]

        def norm(x, scale, eps):        # a forward's 4th: layer 0's
            calls[0] += 1
            return x if calls[0] == 4 else real_norm(x, scale, eps)

        def call(self, *a, **k):
            calls[0] = 0
            return real_call(self, *a, **k)
        patches += [(model_mod, "_rms_norm", norm), (cls, "__call__", call)]
    elif name == "sqrt_h_dropped":
        def fix(engine):
            model = engine.model
            while hasattr(model, "_model"):
                model = model._model
            model.config = dataclasses.replace(model.config,
                                               mup_enabled=False)
            return engine
    elif name == "bias_dropped":
        real = grouped_gemm.sigmoid_bias_topk_routing
        patches.append((grouped_gemm, "sigmoid_bias_topk_routing",
                        lambda logits, bias, *a, **k: real(
                            logits, jnp.zeros_like(bias), *a, **k)))
    elif name == "bias_in_weights":
        def routing(logits, bias, k, renormalize=True, scale=1.0,
                    norm_eps=1e-20):
            s = jax.nn.sigmoid(logits.astype(jnp.float32)) \
                + bias.astype(jnp.float32)
            topw, topi = jax.lax.top_k(s, k)
            if renormalize:
                topw = topw / (jnp.sum(topw, -1, keepdims=True) + norm_eps)
            return topi.astype(jnp.int32), topw * scale
        patches.append((grouped_gemm, "sigmoid_bias_topk_routing", routing))
    elif name == "released_block_read":
        real_pack = engine_v2._pack_window_tables
        real_final = ragged_wrapper.RaggedBatchWrapper.finalize

        def finalize(self, *a, **k):
            meta = real_final(self, *a, **k)
            meta["block_tables_win"] = _stale_entry(meta["block_tables_win"])
            return meta
        patches += [
            (engine_v2, "_pack_window_tables",
             lambda *a, **k: _stale_entry(real_pack(*a, **k))),
            (ragged_wrapper.RaggedBatchWrapper, "finalize", finalize)]
    elif name != "clean":
        raise KeyError(name)
    olds = [(mod, attr, getattr(mod, attr)) for mod, attr, _ in patches]
    for mod, attr, new in patches:
        setattr(mod, attr, new)
    try:
        yield fix
    finally:
        for mod, attr, old in olds:
            setattr(mod, attr, old)


def _cut_mantissa(a):
    """bf16 ``a`` rounded to 3 mantissa bits (float8_e4m3's), in place."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0), jnp.bfloat16)


class _LowPrecisionReference:
    """The family, but for ``reference_params``: the reference reads the
    engine's matrices cut to 3 mantissa bits.  The engine has run by then
    (``_check_logits`` asks for the reference's parameters after the last
    decode step), so they are cut in place: 8.6 GB are not held twice."""

    def __init__(self, family):
        self._family = family

    def __getattr__(self, name):
        return getattr(self._family, name)

    def reference_params(self, params):
        cut = jax.jit(_cut_mantissa, donate_argnums=0)
        return self._family.reference_params(jax.tree.map(
            lambda a: cut(a) if a.ndim >= 2 else a, params))


def cell_engine(cfg, family, seed: int):
    """The cell's engine on seeded weights."""
    from benchmark.runners import serve_ragged
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
    print("seeding: " + ", ".join(
        f"{n} {getattr(family, n)}" for n in ("BIAS_MEAN", "BIAS_STD",
                                              "EXPERT_DOWN")), flush=True)
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [3900000091]:
        for name in ("clean",) + FAULTS + CONTROLS:
            if only and name not in only:
                continue
            control = name in CONTROLS
            with fault("clean" if control else name,
                       int(cfg["sliding_window"])) as fix:
                engine = fix(cell_engine(cfg, family, seed))
                gap = serve_ragged._check_logits(
                    engine, reference,
                    _LowPrecisionReference(family) if control else family,
                    cfg, seed, int(sv["check_prompt_tokens"]),
                    int(sv["check_decode_tokens"]))
            del engine
            gc.collect()    # the step programs' closures hold the engine
            seen = (gap <= tol) if name == "clean" else (gap > tol)
            if name in BY_SEED:
                verdict = "seen" if seen else "not seen on this seed (the " \
                    "routed experts)"
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
