"""One fault at a time in ``RaggedLongcatFlash`` (LongCat-Flash, ``model_type:
longcat_flash``): what ``test_ragged_longcat_flash.py`` applies at tiny sizes
on the CPU and ``benchmark/tools/calls/pr52_faults.py`` at the published
widths on the chip.

The layer's wiring: ``shortcut_early`` (the routed branch's result added
after sub-block 0's dense FFN and not after sub-block 1's), ``branch_from_m1``
(the branch fed sub-block 1's post-attention norm), ``shared_cache_layer``
(both sub-layers of a layer read and write cache layer ``2 l``).  The zero
-compute experts: ``zero_dropped`` (a chosen zero output adds nothing),
``zero_renormalised`` (its weight divided by the sum of the token's twelve
scores, what ``norm_topk_prob`` would do).  The latent scales:
``s_q_missing``, ``s_kv_missing``, ``k_pe_scaled`` (the shared rotated key
times ``s_kv`` too).  The router: ``bias_dropped`` (selection by the scores
alone), ``bias_in_weights`` (the weights are ``score + bias``),
``sigmoid_router`` (sigmoid for softmax).
"""

import contextlib

import jax
import jax.numpy as jnp

WIRING = ("shortcut_early", "branch_from_m1", "shared_cache_layer")
ROUTER = ("zero_dropped", "zero_renormalised", "bias_dropped",
          "bias_in_weights", "sigmoid_router")
SCALES = ("s_q_missing", "s_kv_missing", "k_pe_scaled")
FAULTS = WIRING + ROUTER + SCALES


def _forward(variant):
    """``RaggedLongcatFlash.__call__`` with one of ``WIRING`` in it: the
    same calls in the same order but for that."""
    def call(self, params, cache, batch, prefill_tile=None, decode=False):
        from deepspeed_tpu.inference.v2.model_implementations import \
            ragged_longcat_flash as mod

        cfg, dt = self.config, self.config.dtype
        x = params["embed_tokens"]["embedding"].astype(dt)[
            batch["token_ids"]]
        cos, sin = mod._rotary(batch["token_pos"], cfg.qk_rope_head_dim,
                               cfg.rope_theta)
        real = batch["kv_dest"] >= self.block_size
        new_cache, counts = dict(cache), jnp.zeros((3,), jnp.int32)
        for i in range(cfg.num_layers):
            lp = params[f"layers_{i}"]
            for j in (0, 1):
                sp = lp[f"sub_{j}"]
                name = f"layer_{2 * i}" if variant == "shared_cache_layer" \
                    else f"layer_{2 * i + j}"
                out, new_cache[name] = self._mla(
                    sp, x, new_cache[name], batch, cos, sin, prefill_tile,
                    decode)
                x = x + out
                m = mod._rms_norm(
                    x, sp["post_attention_layernorm"]["scale"],
                    cfg.rms_norm_eps)
                if j == (1 if variant == "branch_from_m1" else 0):
                    y, c = mod.zero_expert_moe(
                        m, lp["mlp"], cfg.moe_topk, dt, cfg.zero_expert_num,
                        expert_start=cfg.expert_start,
                        routed_scale=cfg.routed_scaling_factor, real=real)
                    counts = counts + c
                mlp = sp["mlp"]
                x = x + mod.qmm(
                    jax.nn.silu(mod.qmm(m, mlp["gate_proj"]["kernel"], dt))
                    * mod.qmm(m, mlp["up_proj"]["kernel"], dt),
                    mlp["down_proj"]["kernel"], dt)
                if variant == "shortcut_early" and j == 0:
                    x = x + y
            if variant != "shortcut_early":
                x = x + y
        x = mod._rms_norm(x, params["norm"]["scale"], cfg.rms_norm_eps)
        x = x[batch["logits_idx"]]
        return x @ params["lm_head"]["kernel"].astype(dt), new_cache, counts
    return call


def _routing(variant, zero_experts):
    """``softmax_bias_topk_routing`` with one of ``ROUTER`` in it."""
    def route(logits, bias, k, scale=1.0):
        logits = logits.astype(jnp.float32)
        s = jax.nn.sigmoid(logits) if variant == "sigmoid_router" \
            else jax.nn.softmax(logits, axis=-1)
        b = bias.astype(jnp.float32)
        if variant == "bias_dropped":
            b = jnp.zeros_like(b)
        _, topi = jax.lax.top_k(s + b, k)
        w = jnp.take_along_axis(
            s + b if variant == "bias_in_weights" else s, topi, axis=-1)
        zero = topi >= logits.shape[-1] - zero_experts
        if variant == "zero_dropped":
            w = jnp.where(zero, 0.0, w)
        elif variant == "zero_renormalised":
            w = jnp.where(zero, w / jnp.sum(w, -1, keepdims=True), w)
        return topi.astype(jnp.int32), w * scale
    return route


@contextlib.contextmanager
def fault(name: str, zero_experts: int = 256):
    """The program with one fault in it, for engines built and run inside
    the block (``zero_experts``: the router's zero-compute outputs, which
    the router's own function is not told)."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_deepseek_v3 as mla_mod
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_longcat_flash as mod
    from deepspeed_tpu.ops import grouped_gemm

    cls = mod.RaggedLongcatFlash
    patches = []
    if name in WIRING:
        patches.append((cls, "__call__", _forward(name)))
    elif name in ROUTER:
        patches.append((grouped_gemm, "softmax_bias_topk_routing",
                        _routing(name, zero_experts)))
    elif name in ("s_q_missing", "s_kv_missing"):
        patches.append((mod.LongcatFlashConfig,
                        "q_scale" if name == "s_q_missing" else "kv_scale",
                        property(lambda self: 1.0)))
    elif name == "k_pe_scaled":
        real_rotary = mla_mod.apply_rotary

        def rotary(x, cos, sin):    # the shared key is the one-head call
            out = real_rotary(x, cos, sin)
            if x.shape[1] != 1:
                return out
            s_kv = (6144 / 512) ** 0.5      # the published ratio, any size
            return (out.astype(jnp.float32) * s_kv).astype(out.dtype)
        patches.append((mla_mod, "apply_rotary", rotary))
    elif name != "clean":
        raise ValueError(f"unknown fault {name!r}")
    saved = [(obj, attr, vars(obj)[attr]) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)
