"""The dropless routed-expert FFN of every MoE family, over the flat
``[T, H]`` token buffer (reference: ``kernels/ragged_ops/{top_k_gating,
moe_scatter,moe_gather}/``, ``kernels/cutlass_ops/moe_gemm/``).  Device
scopes: ``moe/router`` (float32 logits, softmax or sigmoid-with-bias, top-k:
★top_k_gating), ``moe/dispatch`` (counting sort of the ``T x k`` routed rows
by expert: ★moe_scatter), ``moe/experts`` (three calls of the grouped GEMM
``ops/grouped_gemm.py::_gmm_kernel`` around the SwiGLU product, each expert
over its own rows, so FLOPs scale with ``k x T``, not ``E x T``: ★moe_gemm;
the XLA composition ``gmm_reference`` off the TPU), ``moe/combine`` (unsort
and weighted sum: ★moe_gather), ``moe/shared``, and for a router with
zero-compute experts ``moe/zero`` (``zero_expert_moe``).  ``grouped=False``
is the dense all-experts parity oracle of the tests and ``chip_smoke.py``
(``E / k`` times the FLOPs), never served.  Dropless gating makes MoE ragged-safe: no
capacity buckets, so pad lanes cannot perturb real tokens' routing."""

import jax
import jax.numpy as jnp


def moe_router(x, wg, k: int, renormalize: bool = True, bias=None,
               routed_scale: float = 1.0, norm_eps=None, scoring=None):
    """Router of the dropless MoE: ``x`` [T, H] (normed) x ``wg`` [H, E] in
    float32 -> (topi [T, k] int32, weights [T, k] float32).  Float32
    products as well as sums: on a TPU a float32 matmul at the default
    precision rounds its operands to bf16, which changes nothing for a bf16
    engine (its activations and weights are bf16 values already) and flips
    routings on near ties for a float32 one.  Softmax then top-k; with a
    selection ``bias`` [E] (static: the router's parameters carry one) the
    sigmoid router of the DeepSeek-V3 family, its weights times
    ``routed_scale``; ``norm_eps`` (static) replaces the constant its
    renormalisation adds to the sum where a family's published code has
    another (LFM2: 1e-6).  ``scoring="softmax"`` (static) with a ``bias``
    is the LongCat-Flash router: softmax scores, the bias in the selection
    only, weights times ``routed_scale`` and never renormalised."""
    from deepspeed_tpu.ops.grouped_gemm import (exact_topk_routing,
                                                sigmoid_bias_topk_routing,
                                                softmax_bias_topk_routing)

    with jax.named_scope("moe/router"):
        logits = jnp.matmul(x.astype(jnp.float32), wg.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)  # [T, E]
        if bias is not None and scoring == "softmax":
            return softmax_bias_topk_routing(logits, bias, k, routed_scale)
        if bias is not None:
            kwargs = {} if norm_eps is None else {"norm_eps": norm_eps}
            return sigmoid_bias_topk_routing(logits, bias, k, renormalize,
                                             routed_scale, **kwargs)
        return exact_topk_routing(logits, k, renormalize)


def dropless_moe(x, moe_params, k: int, dtype, grouped=None,
                 renormalize: bool = True, expert_start: int = 0,
                 routed_scale: float = 1.0, norm_eps=None):
    """Dropless top-k MoE over a flat token buffer.

    x: [T, H]; returns [T, H]. Router math in fp32 (reference TopKGate is
    fp32, sharded_moe.py:348); expert compute in ``dtype``.

    The expert FFN runs through the grouped GEMM kernel
    (ops/grouped_gemm.py — the reference's ★moe_gemm/★moe_scatter/
    ★moe_gather pipeline): tokens are sorted by expert and each expert
    multiplies only its own row block, so FLOPs scale with k·T instead
    of E·T (4× fewer for Mixtral's 8-expert top-2, 8x for OLMoE's 64 at
    top-8).  ``grouped=False`` forces the dense all-experts einsum (the
    parity oracle).  ``renormalize`` (static) is HF ``norm_topk_prob``.

    A share of the experts (static, read from the shapes: the expert
    matrices hold fewer experts than the router has outputs): the router
    still scores every expert and takes the top-k of all of them, and the
    result is the part of the sum that the held experts
    ``[expert_start, expert_start + held)`` give; a token routed wholly
    elsewhere gets zeros.  With every expert held this is the path above,
    unchanged.  A shared expert (``shared_expert`` in ``moe_params``; gated
    where ``shared_expert_gate`` is there too) is added for every token.
    A router whose parameters carry ``e_score_correction_bias`` is the
    sigmoid router with a selection bias, its weights times
    ``routed_scale`` (static), renormalised with ``norm_eps`` (static; None:
    the router's own constant).
    """
    from deepspeed_tpu.ops.grouped_gemm import grouped_moe_ffn

    wg = moe_params["gate"]["wg"]["kernel"]            # [H, E]
    experts = moe_params["experts"]
    topi, w = moe_router(
        x, wg, k, renormalize,
        bias=moe_params["gate"].get("e_score_correction_bias"),
        routed_scale=routed_scale, norm_eps=norm_eps)  # [T, k]
    e_count = wg.shape[1]
    w_gate = experts["w_gate"].astype(dtype)           # [E, H, F]
    w_up = experts["w_up"].astype(dtype)
    w_down = experts["w_down"].astype(dtype)
    # a share and a shared expert exist on the grouped path only (the
    # dense composition below is the all-experts parity oracle)
    share = w_gate.shape[0] != e_count
    shared = "shared_expert" in moe_params
    if share or shared or grouped is None or grouped:
        kwargs = {"expert_start": int(expert_start)} if share else {}
        out = grouped_moe_ffn(x.astype(dtype), topi, w.astype(dtype),
                              w_gate, w_up, w_down, **kwargs)
        if shared:
            out = out + _shared_expert(x.astype(dtype), moe_params, dtype)
        return out
    # dense all-experts composition (reference/oracle path)
    comb = jnp.sum(jax.nn.one_hot(topi, e_count, dtype=jnp.float32)
                   * w[..., None], axis=1)             # [T, E]
    xe = x.astype(dtype)
    h = jax.nn.silu(jnp.einsum("tm,emf->etf", xe, w_gate)) * \
        jnp.einsum("tm,emf->etf", xe, w_up)            # [E, T, F]
    out = jnp.einsum("etf,efm->etm", h, w_down)        # [E, T, H]
    return jnp.einsum("te,etm->tm", comb.astype(dtype), out)


def zero_expert_moe(x, moe_params, k: int, dtype, zero_experts: int,
                    expert_start: int = 0, routed_scale: float = 1.0,
                    real=None):
    """The routed branch of a router whose LAST ``zero_experts`` outputs
    are zero-compute experts (LongCat-Flash, ``zero_expert_type:
    identity``): softmax over every output, the top-k of ``score + bias``,
    weights the unbiased scores times ``routed_scale``.  A chosen expert
    adds ``w E(x)`` through the grouped GEMMs (only the held ones
    ``[expert_start, expert_start + held)``, as ``dropless_moe``; the zero
    outputs lie past every expert and leave before the sort like an expert
    held elsewhere); a chosen zero output costs nothing and adds ``w x``:
    device scope ``moe/zero``, the sum of a token's zero slots' weights and
    one multiply-add a value.  x: [T, H]; returns ``(out [T, H], counts)``:
    ``counts`` is None, or with ``real`` ([T] bool: the rows that are no
    padding) int32[3], over the real rows: routed slots (``k`` a row),
    slots that chose a zero output, slots whose expert is held here."""
    from deepspeed_tpu.ops.grouped_gemm import grouped_moe_ffn

    gate, experts = moe_params["gate"], moe_params["experts"]
    wg = gate["wg"]["kernel"]                           # [H, E + Z]
    n_experts = wg.shape[1] - zero_experts
    topi, w = moe_router(x, wg, k, bias=gate["e_score_correction_bias"],
                         routed_scale=routed_scale, scoring="softmax")
    held = experts["w_gate"].shape[0]
    out = grouped_moe_ffn(
        x.astype(dtype), topi, w.astype(dtype),
        experts["w_gate"].astype(dtype), experts["w_up"].astype(dtype),
        experts["w_down"].astype(dtype), expert_start=int(expert_start))
    with jax.named_scope("moe/zero"):
        zero = topi >= n_experts
        w_zero = jnp.sum(jnp.where(zero, w, 0.0), axis=-1, keepdims=True)
        out = (out.astype(jnp.float32)
               + w_zero * x.astype(jnp.float32)).astype(dtype)
    counts = None
    if real is not None:
        with jax.named_scope("moe/router"):
            here = (topi >= expert_start) & (topi < expert_start + held)
            rows = real[:, None]
            counts = jnp.stack([
                k * jnp.sum(real), jnp.sum(zero & rows),
                jnp.sum(here & rows)]).astype(jnp.int32)
    return out, counts


def _shared_expert(x, moe_params, dtype):
    """The expert every token takes (device scope ``moe/shared``):
    ``down(silu(gate x) * up x)``, times ``sigmoid(x . w_sg)`` where the
    parameters carry that gate (static)."""
    with jax.named_scope("moe/shared"):
        se = moe_params["shared_expert"]
        hmid = jax.nn.silu(x @ se["gate_proj"]["kernel"].astype(dtype)) \
            * (x @ se["up_proj"]["kernel"].astype(dtype))
        y = hmid @ se["down_proj"]["kernel"].astype(dtype)
        if "shared_expert_gate" not in moe_params:
            return y
        sg = jax.nn.sigmoid(
            x.astype(jnp.float32)
            @ moe_params["shared_expert_gate"]["kernel"].astype(jnp.float32))
        return (sg * y.astype(jnp.float32)).astype(dtype)
