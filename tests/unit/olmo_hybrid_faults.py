"""One fault at a time in ``RaggedOlmoHybrid`` (Olmo-Hybrid, ``model_type:
olmo_hybrid``): what ``test_ragged_olmo_hybrid.py`` applies at tiny sizes on
the CPU and ``benchmark/tools/calls/pr56_faults.py`` at the published widths
on the chip.

The state: ``carry_dropped`` (every prompt chunk starts from a zeroed
recurrent state: what a lost slot or a spurious reset does),
``tail_dropped`` (every chunk's convolution starts from a zeroed tail).
The mathematics: ``beta_unit`` (write strengths ``sigmoid(b)`` in (0, 1):
``linear_allow_neg_eigval`` ignored), ``pre_norm`` (the block's norms on
each sub-layer's INPUT and none on its output), ``rotary`` (rotate-half at
theta 10000 on the attention layers' q and k).  The precision:
``state_bf16`` (the recurrent state rounded to bf16 wherever a rule writes
it), ``products_bf16`` (the rules' float32 products at
``Precision.DEFAULT``, one bf16 pass for six: Mosaic lowers a float32
product at ``HIGHEST`` or ``DEFAULT`` only and refuses ``Precision.HIGH``
by name, so the three-pass form ISSUE 56 names cannot be built on the
chip).

A non-zero value in a padded lane or a dead head, which ISSUE 56 lists, has
no place to stand: the state is stored as head pairs ``[15, 96, 384]``
(since PR 58; ``[30, 96, 192]`` before), with no dead head and no padded
lane at all.  Every fault here is blind to that layout: the rules are
patched at their public entries, which take and return the pool as stored.
"""

import contextlib

import jax
import jax.numpy as jnp

STATE = ("carry_dropped", "tail_dropped")
MATHS = ("beta_unit", "pre_norm", "rotary")
PRECISION = ("state_bf16", "products_bf16")
FAULTS = STATE + MATHS + PRECISION


def _pre_norm_call(self, params, cache, batch, prefill_tile=None,
                   decode=False):
    """``RaggedOlmoHybrid.__call__`` as a PRE-norm block: the same weights
    normalise each sub-layer's input."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_olmo_hybrid as mod

    cfg = self.config
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    x = params["embed_tokens"]["embedding"].astype(dt)[batch["token_ids"]]
    h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    new_cache = {}
    for i in range(cfg.num_hidden_layers):
        lp = params[f"layers_{i}"]
        xn = mod._rms_norm(x, lp["post_attention_layernorm"]["scale"], eps)
        if "linear_attn" in lp:
            out, new_cache[f"layer_{i}"] = mod.gdn_mixer(
                lp["linear_attn"], xn, cache[f"layer_{i}"], batch,
                prefill_tile, cfg, interpret=self.interpret)
        else:
            out, new_cache[f"layer_{i}"] = mod.ragged_attention_block(
                lp["self_attn"], xn, cache[f"layer_{i}"], batch,
                self.block_size, cfg, h, hkv, d, None, None,
                prefill_tile=prefill_tile, decode_mode=decode)
        x = x + out
        xn = mod._rms_norm(x, lp["post_feedforward_layernorm"]["scale"],
                           eps)
        mlp = lp["mlp"]
        x = x + mod.qmm(
            jax.nn.silu(mod.qmm(xn, mlp["gate_proj"]["kernel"], dt))
            * mod.qmm(xn, mlp["up_proj"]["kernel"], dt),
            mlp["down_proj"]["kernel"], dt)
    x = mod._rms_norm(x, params["norm"]["scale"], eps)[batch["logits_idx"]]
    return x @ params["lm_head"]["kernel"].astype(dt), new_cache


@contextlib.contextmanager
def fault(name: str):
    """Patch one fault into the program for the ``with`` block (``clean``:
    none).  Engines and step programs must be BUILT inside it."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_olmo_hybrid as mod
    from deepspeed_tpu.inference.v2.modules import attention, conv, gdn
    from deepspeed_tpu.ops import gated_delta_rule as gdr

    saved = []

    def patch(obj, attr, new):
        saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "carry_dropped":
        real = gdn.gdn_chunk
        patch(gdn, "gdn_chunk", lambda pool, q, k, v, g, beta, slot, reset,
              tile, interpret=None: real(pool, q, k, v, g, beta, slot,
                                         jnp.ones_like(reset), tile,
                                         interpret=interpret))
    elif name == "tail_dropped":
        real_conv = conv._causal_conv
        patch(gdn, "_causal_conv", lambda u, w, pool, batch, **kw: real_conv(
            u, w, jnp.zeros_like(pool), batch, **kw))
    elif name == "beta_unit":
        real_mixer = gdn.gdn_mixer

        class _Unit:
            def __init__(self, cfg):
                self._cfg = cfg

            def __getattr__(self, key):
                return False if key == "linear_allow_neg_eigval" \
                    else getattr(self._cfg, key)

        patch(mod, "gdn_mixer", lambda la, x, lc, batch, tile, cfg, **kw:
              real_mixer(la, x, lc, batch, tile, _Unit(cfg), **kw))
    elif name == "pre_norm":
        patch(mod.RaggedOlmoHybrid, "__call__", _pre_norm_call)
    elif name == "rotary":
        real_block = attention.ragged_attention_block

        def block(lp, xa, lc, batch, bs, cfg, h, hkv, d, cos, sin, **kw):
            cos, sin = attention._rotary(batch["token_pos"], d, 10000.0)
            return real_block(lp, xa, lc, batch, bs, cfg, h, hkv, d, cos,
                              sin, **kw)

        patch(mod, "ragged_attention_block", block)
    elif name == "state_bf16":
        cut = lambda p: p.astype(jnp.bfloat16).astype(p.dtype)
        real_step, real_chunk = gdn.gdn_step, gdn.gdn_chunk

        def step(*a, **kw):
            o, pool = real_step(*a, **kw)
            return o, cut(pool)

        def chunk(*a, **kw):
            o, pool = real_chunk(*a, **kw)
            return o, cut(pool)

        patch(gdn, "gdn_step", step)
        patch(gdn, "gdn_chunk", chunk)
    elif name == "products_bf16":
        patch(gdr, "_HI", jax.lax.Precision.DEFAULT)
        gdr._gdn_chunk_call.clear_cache()
    elif name != "clean":
        raise ValueError(f"unknown fault {name!r}: one of {FAULTS}")
    try:
        yield
    finally:
        for obj, attr, old in reversed(saved):
            setattr(obj, attr, old)
        if name == "products_bf16":
            gdr._gdn_chunk_call.clear_cache()
