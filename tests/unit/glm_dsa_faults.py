"""One fault at a time in the learned sparse-attention indexer of
``RaggedDeepseekV3`` (GLM-5, ``model_type: glm_moe_dsa``): what
``test_ragged_glm_dsa.py`` applies at tiny sizes on the CPU and
``benchmark/tools/calls/pr50_faults.py`` at the published widths on the chip.

``indexer_dropped``: every row reads every cached position (the dense latent
read under GLM-5's name).  ``recent_topk``: the most recent ``index_topk``
positions in place of the best-scored.  ``k_off_by_block``: ``index_topk``
less one block.  ``indexer_rope_missing``: the indexer's queries and keys
unrotated (the main rope kept).  ``idx_row_fp8``: the ``idx_k`` leaf read at
float8_e4m3's 3 mantissa bits, a precision below the bf16 the configuration
states for it.  ``w_scale_missing``: ``w`` without ``HI^-0.5 DI^-0.5`` (a
positive factor on every score of a row changes no order).
``k_norm_bias_dropped``: the indexer key's LayerNorm without its bias.
"""

import contextlib

import jax
import jax.numpy as jnp


def _cut_mantissa(a):
    """bf16 ``a`` rounded to 3 mantissa bits (float8_e4m3's)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint16)
    return jax.lax.bitcast_convert_type(
        (bits + jnp.uint16(8)) & jnp.uint16(0xFFF0), jnp.bfloat16)


@contextlib.contextmanager
def fault(name: str, block: int = 128):
    """The program with one fault in it, for engines built and run inside
    the block (``block``: the pool's block size, what ``k_off_by_block``
    takes off)."""
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_deepseek_v3 as model_mod

    cls = model_mod.RaggedDeepseekV3
    real_scores, real_topk, real_thr = model_mod.index_scores, \
        model_mod.select_topk, model_mod.select_threshold
    real_read = cls._sparse_read
    patches = []
    if name == "indexer_dropped":
        everything = 1 << 30
        patches += [
            (model_mod, "select_topk",
             lambda scores, k: real_topk(scores, everything)),
            (model_mod, "select_threshold",
             lambda key, k, **kw: real_thr(key, everything, **kw))]
    elif name == "recent_topk":
        def scores(*a, **k):
            s = real_scores(*a, **k)
            place = jnp.arange(s.shape[-1], dtype=jnp.float32)
            return jnp.where(jnp.isfinite(s), place, s)
        patches.append((model_mod, "index_scores", scores))
    elif name == "k_off_by_block":
        patches += [
            (model_mod, "select_topk",
             lambda scores, k: real_topk(scores, k - block)),
            (model_mod, "select_threshold",
             lambda key, k, **kw: real_thr(key, k - block, **kw))]
    elif name == "indexer_rope_missing":
        def read(self, att, xa, cq, q_nope, q_pe, pool, idx_pool, batch,
                 cos, sin, *a, **k):
            return real_read(self, att, xa, cq, q_nope, q_pe, pool, idx_pool,
                             batch, jnp.ones_like(cos), jnp.zeros_like(sin),
                             *a, **k)
        patches.append((cls, "_sparse_read", read))
    elif name == "idx_row_fp8":
        def scores(q, w, idx_pool, *a, **k):
            # (by the bits: XLA drops a convert to float8 and back as excess
            # precision, and the first chip reading of this fault was the
            # clean program's to five digits)
            low = _cut_mantissa(idx_pool.astype(jnp.bfloat16))
            return real_scores(q, w, low.astype(idx_pool.dtype), *a, **k)
        patches.append((model_mod, "index_scores", scores))
    elif name == "w_scale_missing":
        def scores(q, w, *a, **k):
            hi, di = q.shape[-2:]
            return real_scores(q, w * float(hi ** 0.5 * di ** 0.5), *a, **k)
        patches.append((model_mod, "index_scores", scores))
    elif name == "k_norm_bias_dropped":
        real_norm = model_mod._layer_norm
        patches.append((model_mod, "_layer_norm", lambda x, p, eps: real_norm(
            x, {**p, "bias": jnp.zeros_like(p["bias"])}, eps)))
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
