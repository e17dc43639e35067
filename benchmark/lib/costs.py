"""Operations and bytes an algorithm needs, from shapes alone, and the
roofline arithmetic against ``peaks.json``.  Kept with the benchmark so no
PR that claims a gain can change how its share of the roofline is computed.
``shapes`` is what ``families/<family>.py::shapes`` returns.

Conventions: a multiply-add is 2 FLOPs; causal attention counts half of the
S x S score matrix; the backward pass counts twice the forward; recomputed
operations (flash attention's backward recomputes the scores) do NOT count —
these are the operations the mathematics requires, not the ones a kernel
chose to execute.
"""

from __future__ import annotations

from typing import Dict, Tuple


def attention_fwd_flops_per_token(shapes: Dict[str, int], seq: int) -> float:
    """QK^T and PV of one causal layer, per token: 2 matmuls x 2 FLOPs x
    (seq / 2 visible keys on average) x q_heads x head_dim."""
    return 2.0 * seq * shapes["q_heads"] * shapes["head_dim"]


def train_flops_per_token(shapes: Dict[str, int], seq: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per matmul
    parameter (embedding lookup excluded, lm_head included) plus three
    times the attention forward in every layer."""
    return 6.0 * shapes["matmul_params"] + shapes["layers"] * 3.0 * \
        attention_fwd_flops_per_token(shapes, seq)


def train_attention_step_costs(shapes: Dict[str, int], batch: int, seq: int,
                               dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) all attention layers of one fwd+bwd step need.
    Bytes: the forward reads q, k, v and writes o; the backward reads q, k,
    v, o, do and writes dq, dk, dv — q-sized tensors 6 times, kv-sized 6
    times (the per-row logsumexp is left out: 1/head_dim of a tensor)."""
    tokens = batch * seq
    flops = shapes["layers"] * tokens * 3.0 * \
        attention_fwd_flops_per_token(shapes, seq)
    per_token = 6 * (shapes["q_heads"] + shapes["kv_heads"]) * \
        shapes["head_dim"] * dtype_bytes
    return flops, float(shapes["layers"] * tokens * per_token)


def decode_tick_bytes(shapes: Dict[str, int], weight_bytes: int,
                      live_tokens: int) -> float:
    """HBM bytes one pure-decode tick must read: every weight once plus the
    keys and values of every live context token."""
    return float(weight_bytes + live_tokens * shapes["kv_bytes_per_token"])


def roofline(flops: float, nbytes: float, seconds: float,
             peaks: Dict[str, float]) -> Dict[str, float]:
    """Share of the roofline reached: the least time the chip could take
    (the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s) over
    the measured time; ``bound`` says which of the two it was."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return {"pct": 100.0 * least / seconds if seconds > 0 else float("nan"),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "least_s": least}


def mfu_pct(shapes: Dict[str, int], seq: int, tokens_per_s: float,
            chips: int, peaks: Dict[str, float]) -> float:
    """Model FLOP/s utilization: required FLOPs per token x tokens/s over
    chips x peak."""
    return 100.0 * train_flops_per_token(shapes, seq) * tokens_per_s / \
        (chips * peaks["bf16_flops_per_s"])
