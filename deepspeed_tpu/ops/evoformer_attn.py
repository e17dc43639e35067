"""Evoformer attention — DS4Science (reference:
csrc/deepspeed4science/evoformer_attn/ CUTLASS fused MHA with broadcast
pair biases, ~14.9k LoC — the kernel family exists precisely to avoid
materialising the [*, heads, seq_q, seq_k] score tensor at AlphaFold
shapes; python surface deepspeed/ops/deepspeed4science/evoformer_attn.py
``DS4Sci_EvoformerAttention``; built by op_builder/evoformer_attn.py).

TPU form: a BLOCKWISE PAIR-BIAS FLASH Pallas kernel — the two broadcast
biases (MSA mask bias and the pair-representation bias) are folded into
the online-softmax tiles of the same machinery as
ops/flash_attention.py, so the fp32 live set per grid step is one
[block_q, block_k] tile and the O(S²·rows) score buffer never exists in
HBM.  Bias broadcasting (e.g. mask [B, R, 1, 1, Sk], pair
[B, 1, H, Sq, Sk]) is resolved by the BLOCK-SPEC INDEX MAPS: a broadcast
dim maps to block 0, so each grid step DMAs only the bias tile it
actually reads — the pair bias is streamed once per (h, q, k) tile
combination regardless of the number of MSA rows.

The backward runs the dense composition CHUNKED over the flattened lead
dim via ``lax.map`` (one [H, Sq, Sk] slice live at a time), so training
memory is bounded by a single lead slice instead of the full batch — the
pair-bias gradient (summed over broadcast dims) comes out of the chunk
VJPs.  The dense composition remains the CPU/odd-shape path and the
parity oracle.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.flash_attention import NEG_INF
from deepspeed_tpu.utils.platform import kernel_names, on_tpu

__all__ = ["DS4Sci_EvoformerAttention", "EvoformerAttnBuilder",
           "evoformer_attention_dense"]


def evoformer_attention_dense(Q, K, V, biases=None):
    """Dense composition (parity oracle / fallback): materialises the
    score tensor."""
    *lead, sq, h, d = Q.shape
    scale = 1.0 / float(np.sqrt(d))
    q = jnp.moveaxis(Q.astype(jnp.float32), -2, -3)   # [..., h, sq, d]
    k = jnp.moveaxis(K.astype(jnp.float32), -2, -3)
    v = jnp.moveaxis(V.astype(jnp.float32), -2, -3)
    scores = jnp.einsum("...hqd,...hkd->...hqk", q, k) * scale
    for bias in biases or []:
        scores = scores + bias.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("...hqk,...hkd->...hqd", probs, v)
    return jnp.moveaxis(out, -3, -2).astype(Q.dtype)


# --------------------------------------------------------------------- #
# Pallas blockwise kernel
# --------------------------------------------------------------------- #
def _evo_kernel(q_ref, k_ref, v_ref, *rest, num_biases: int,
                num_k_blocks: int, scale: float):
    bias_refs = rest[:num_biases]
    o_ref = rest[num_biases]
    acc_ref, m_ref, l_ref = rest[num_biases + 1:]
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0]                                    # [bq, d]
    kb = k_ref[0, 0]                                   # [bk, d]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), kb.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale    # [bq, bk]
    for b_ref in bias_refs:
        # bias tile [1, 1, bq|1, bk|1] broadcasts over the score tile
        s = s + b_ref[0, 0].astype(jnp.float32)
    m_prev = m_ref[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True),
        l_ref.shape)
    vb = v_ref[0, 0]                                   # [bk, d]
    acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == num_k_blocks - 1)
    def _():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _canon_bias(b, lead: Tuple[int, ...], h: int, sq: int, sk: int):
    """Left-pad a bias to rank len(lead)+3 (each dim full-size or 1) and
    validate broadcastability — no broadcast materialisation."""
    want = len(lead) + 3
    if b.ndim < want:
        b = b.reshape((1,) * (want - b.ndim) + b.shape)
    for i, (bd, full) in enumerate(zip(b.shape, tuple(lead) + (h, sq, sk))):
        if bd not in (1, full):
            raise ValueError(
                f"bias dim {i} = {bd} not broadcastable to {full}")
    return b


def _bias_lead_index(lead: Tuple[int, ...], bias_lead: Tuple[int, ...]):
    """Return f(l) mapping the flattened lead index to the bias's
    flattened (broadcast-aware) lead index — static strides only."""
    # divisor to extract coordinate i from l
    divs = []
    acc = 1
    for s in reversed(lead):
        divs.append(acc)
        acc *= s
    divs = list(reversed(divs))                       # [prod(lead[i+1:])]
    # bias strides over its own (size-1-aware) lead dims
    bstrides = []
    bacc = 1
    for s in reversed(bias_lead):
        bstrides.append(bacc)
        bacc *= s
    bstrides = list(reversed(bstrides))
    terms = [(divs[i], lead[i], bstrides[i])
             for i in range(len(lead)) if bias_lead[i] != 1]

    def f(l):
        lb = 0
        for div, mod, stride in terms:
            lb = lb + ((l // div) % mod) * stride
        return lb

    return f


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "lead"))
def _evo_kernel_call(q, k, v, biases, lead: Tuple[int, ...],
                     block_q: int, block_k: int, interpret: bool):
    # q/k/v arrive flattened AND head-major: [L, H, S, D] — the TPU
    # block constraint wants the last two block dims (seq tile, head
    # dim) to be (8k, full)
    L, h, sq, d = q.shape
    sk = k.shape[2]
    nq, nk = sq // block_q, sk // block_k
    scale = 1.0 / float(np.sqrt(d))

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda l, ih, iq, ik: (l, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda l, ih, iq, ik: (l, ih, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda l, ih, iq, ik: (l, ih, ik, 0)),
    ]
    ops = [q, k, v]
    for b in biases:
        blead, (bh, bsq, bsk) = b.shape[:-3], b.shape[-3:]
        bflat = b.reshape((int(np.prod(blead)) if blead else 1,
                           bh, bsq, bsk))
        lead_ix = _bias_lead_index(lead, blead)
        bq_blk = block_q if bsq != 1 else 1
        bk_blk = block_k if bsk != 1 else 1

        def mk_index(lead_ix=lead_ix, bh=bh, bsq=bsq, bsk=bsk):
            def ix(l, ih, iq, ik):
                return (lead_ix(l), ih if bh != 1 else 0,
                        iq if bsq != 1 else 0, ik if bsk != 1 else 0)
            return ix

        in_specs.append(pl.BlockSpec((1, 1, bq_blk, bk_blk), mk_index()))
        ops.append(bflat)

    kernel = functools.partial(
        _evo_kernel, num_biases=len(biases), num_k_blocks=nk, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(L, h, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda l, ih, iq, ik: (l, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((L, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        **kernel_names(kernel),
    )(*ops)


def _pick_block(n: int, target: int) -> Optional[int]:
    """Largest divisor of n that is <= target AND a multiple of 8 (TPU
    sublane tiling); None when no aligned block exists (caller falls
    back to the dense composition)."""
    b = (min(n, target) // 8) * 8
    while b >= 8:
        if n % b == 0:
            return b
        b -= 8
    return None


def _flash_path(Q, K, V, biases, interpret):
    *lead, sq, h, d = Q.shape
    sk = K.shape[-3]
    lead = tuple(lead)
    L = int(np.prod(lead)) if lead else 1
    bq = _pick_block(sq, 256)
    bk = _pick_block(sk, 256)
    canon = tuple(_canon_bias(b, lead, h, sq, sk) for b in biases)

    def hm(x, s):  # [*, s, h, d] -> [L, h, s, d] (head-major)
        return jnp.moveaxis(x.reshape((L, s, h, d)), 1, 2)

    out = _evo_kernel_call(hm(Q, sq), hm(K, sk), hm(V, sk), canon, lead,
                           bq, bk, bool(interpret))
    return jnp.moveaxis(out, 1, 2).reshape(Q.shape)


# --------------------------------------------------------------------- #
# Public entry with chunked-recompute backward
# --------------------------------------------------------------------- #
def _bwd_chunked(res, dout):
    """Dense recompute + VJP one lead slice at a time (lax.map), so the
    backward's live set is one [H, Sq, Sk] score slice; the broadcast
    biases' gradients accumulate across chunks via the sum lax.map
    performs implicitly... (we sum explicitly below)."""
    Q, K, V, biases = res
    *lead, sq, h, d = Q.shape
    lead = tuple(lead)
    L = int(np.prod(lead)) if lead else 1
    qf = Q.reshape((L,) + Q.shape[len(lead):])
    kf = K.reshape((L,) + K.shape[len(lead):])
    vf = V.reshape((L,) + V.shape[len(lead):])
    dof = dout.reshape((L,) + dout.shape[len(lead):])
    sk = K.shape[-3]
    canon = [ _canon_bias(b, lead, h, sq, sk) for b in biases ]
    lead_maps = [_bias_lead_index(lead, b.shape[:-3]) for b in canon]
    bflat = [b.reshape((-1,) + b.shape[-3:]) for b in canon]

    def one(args):
        l, ql, kl, vl, dol = args
        bs = [bf[lm(l)] for bf, lm in zip(bflat, lead_maps)]

        def f(q_, k_, v_, *bs_):
            return evoformer_attention_dense(q_, k_, v_, list(bs_))

        _out, vjp = jax.vjp(f, ql, kl, vl, *bs)
        return vjp(dol)

    grads = jax.lax.map(
        one, (jnp.arange(L, dtype=jnp.int32), qf, kf, vf, dof))
    dQ = grads[0].reshape(Q.shape)
    dK = grads[1].reshape(K.shape)
    dV = grads[2].reshape(V.shape)
    dbs = []
    for i, b in enumerate(biases):
        g = grads[3 + i]                      # [L, bh, bsq, bsk]
        cb = canon[i]
        blead = cb.shape[:-3]
        # fold the chunk axis back into the bias's own lead extent:
        # chunks sharing a bias slice (broadcast lead dims) SUM
        lb = int(np.prod(blead)) if blead else 1
        if lb == L:
            g = g.reshape(cb.shape)
        else:
            seg = jnp.asarray([lead_maps[i](l) for l in range(L)],
                              jnp.int32)
            g = jax.ops.segment_sum(g, seg, num_segments=lb).reshape(
                cb.shape)
        dbs.append(g.reshape(b.shape).astype(b.dtype))
    return (dQ.astype(Q.dtype), dK.astype(K.dtype), dV.astype(V.dtype),
            tuple(dbs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _evoformer(Q, K, V, biases: Tuple, interpret):
    if interpret is None and not on_tpu():
        return evoformer_attention_dense(Q, K, V, list(biases))
    return _flash_path(Q, K, V, biases, interpret or False)


def _evo_fwd(Q, K, V, biases, interpret):
    return _evoformer(Q, K, V, biases, interpret), (Q, K, V, biases)


def _evo_bwd(interpret, res, dout):
    return _bwd_chunked(res, dout)


_evoformer.defvjp(_evo_fwd, _evo_bwd)


def DS4Sci_EvoformerAttention(Q: jnp.ndarray, K: jnp.ndarray,
                              V: jnp.ndarray,
                              biases: Optional[List[jnp.ndarray]] = None,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """Fused evoformer MHA (reference evoformer_attn.py API).

    Q/K/V: [..., seq, heads, head_dim]; each bias broadcastable to
    [..., heads, seq_q, seq_k] (the reference takes [mask_bias,
    pair_bias]). Returns attention output in Q's layout and dtype.

    On TPU the forward is the blockwise pair-bias flash kernel (no
    O(seq²) HBM buffer); gradients recompute densely one lead slice at a
    time.  ``interpret`` forces the kernel (interpret mode) off-TPU for
    tests; the dense composition remains the default CPU path.
    """
    bs = tuple(biases or [])
    sq, sk = Q.shape[-3], K.shape[-3]
    use_kernel = ((interpret is not None or on_tpu())
                  and Q.shape[-1] % 8 == 0
                  and _pick_block(sq, 256) is not None
                  and _pick_block(sk, 256) is not None)
    if not use_kernel:
        return evoformer_attention_dense(Q, K, V, list(bs))
    return _evoformer(Q, K, V, bs, interpret)


# --------------------------------------------------------------------- #
# dslint contract-checker registration (see analysis/pallas_lint.py):
# the selftest AlphaFold-ish shape with a broadcast pair bias (the
# broadcast-dim->block-0 index maps are exactly what the bounds check
# needs to see).
# --------------------------------------------------------------------- #
from deepspeed_tpu.analysis.registry import pallas_kernel_case  # noqa: E402


@pallas_kernel_case("evoformer_attn",
                    note="pair-bias flash fwd with broadcast bias specs")
def _dslint_evoformer_case():
    rng = np.random.default_rng(4)
    mk = lambda shape: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32), jnp.bfloat16)
    Q, K, V = (mk((1, 4, 256, 4, 32)) for _ in range(3))
    pair = mk((1, 1, 4, 256, 256))
    DS4Sci_EvoformerAttention(Q, K, V, [pair], interpret=True)


class EvoformerAttnBuilder:
    NAME = "evoformer_attn"

    def load(self):
        import deepspeed_tpu.ops.evoformer_attn as m
        return m

    def is_compatible(self) -> bool:
        return True
