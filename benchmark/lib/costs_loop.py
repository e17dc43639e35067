"""Operations and bytes a LOOPED model's ticks need, from shapes alone (a
stack of layers run ``passes`` times a token, a K/V cache per (layer, pass)).
``shapes`` is what ``families/ouro.py::shapes`` returns: ``loop_matmul_params``
the matmul parameters of the looped stack, ``head_params`` the head's,
``kv_bytes_per_token`` what one cached token holds across every cache layer.

What the mathematics requires ON ONE CHIP: the stack's weights (4.9 GB at the
published sizes) cannot stay in the chip's fast memory between passes, so a
tick reads them once a pass; ``lib/costs.py::decode_tick_bytes`` counts "every
weight once", which for this model is a quarter of it.  Conventions as
``lib/costs.py``: a multiply-add is 2 FLOPs, a causal (query, key) pair is 4 x
q_heads x head_dim FLOPs a cache layer (QK^T and PV); norms, the rotation, the
embedding rows and the K/V written are left out of both counts (under a
thousandth of either), so the shares read a little low, never high.
"""

from __future__ import annotations

from typing import Dict


def decode_tick_bytes(shapes: Dict[str, int], ctx_tokens: int,
                      dtype_bytes: int = 2) -> float:
    """HBM bytes one pure-decode tick must read: the looped stack's matmul
    weights once a pass, the head once, and the keys and values of every
    context token of its rows in every cache layer."""
    return float(dtype_bytes * (shapes["passes"] * shapes["loop_matmul_params"]
                                + shapes["head_params"])
                 + ctx_tokens * shapes["kv_bytes_per_token"])


def tick_flops(shapes: Dict[str, int], tokens: int, logit_rows: int,
               attn_pairs: int) -> float:
    """FLOPs one tick must do: every fed token through the looped stack's
    matmuls once a pass, the head at the rows whose logits are asked for,
    and the causal (query, key) pairs of its rows in every cache layer."""
    return (2.0 * tokens * shapes["passes"] * shapes["loop_matmul_params"]
            + 2.0 * logit_rows * shapes["head_params"]
            + 4.0 * attn_pairs * shapes["q_heads"] * shapes["head_dim"]
            * shapes["cache_layers"])
