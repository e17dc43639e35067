"""Operations and bytes the WHOLE tick of a hybrid model needs (linear
-attention layers with per-sequence recurrent state beside softmax-attention
layers with a paged KV cache), from shapes and the program's own counters.
``lib/costs.py::decode_tick_bytes`` counts weights and keys; a third of this
model's decode tick is STATE, so this count has it.  ``shapes`` is what
``families/olmo_hybrid.py::shapes`` returns (``matmul_params`` with the head
in it, ``head_params``, ``kv_bytes_per_token`` over the attention layers,
``gdn_*`` for ``lib/costs_gdn.py``, ``gdn_conv_channels`` /
``gdn_conv_taps``).

Conventions as ``lib/costs.py``: a multiply-add is 2 FLOPs; a causal
(query, key) pair is 4 x q_heads x head_dim FLOPs an attention layer (QK^T
and PV); these are what the MATHEMATICS requires on one chip, not what a
layout holds (a float32 ``[96, 192]`` state row stored as 256 lanes moves a
third more bytes than is counted here, and shows as share lost).  Norms, the
embedding rows, the K/V written, the convolution's own multiplies and the
gates are left out of both counts (under a hundredth of either), so the
shares read a little low, never high.
"""

from __future__ import annotations

from typing import Dict

from benchmark.lib import costs_gdn

TAIL_BYTES = 2          # the convolution tail in the model's bf16


def conv_tail_bytes(shapes: Dict[str, int]) -> int:
    """One sequence's convolution tail in ONE linear-attention layer: the
    last ``taps - 1`` inputs of every channel."""
    return (shapes["gdn_conv_taps"] - 1) * shapes["gdn_conv_channels"] \
        * TAIL_BYTES


def state_bytes(shapes: Dict[str, int], state_seqs: int) -> float:
    """Bytes of recurrent state a launch moves for ``state_seqs`` sequences
    in ALL linear-attention layers: each sequence's matrices and its
    convolution tail read once and written once."""
    return float(shapes["gdn_layers"] * state_seqs * 2 * (
        costs_gdn.state_matrix_bytes(shapes) + conv_tail_bytes(shapes)))


def decode_tick_bytes(shapes: Dict[str, int], ctx_tokens: int,
                      state_seqs: int, dtype_bytes: int = 2) -> float:
    """HBM bytes one pure-decode tick must move: every matmul weight once
    (the head among them), the keys and values of every context token its
    rows read in every attention layer, and the state of its sequences read
    and written in every linear-attention layer."""
    return float(dtype_bytes * shapes["matmul_params"]
                 + ctx_tokens * shapes["kv_bytes_per_token"]) \
        + state_bytes(shapes, state_seqs)


def tick_flops(shapes: Dict[str, int], tokens: int, logit_rows: int,
               attn_pairs: int) -> float:
    """FLOPs one tick must do: every fed token through the layers' matmuls
    and the delta rule of every linear-attention layer, the head at the
    rows whose logits are asked for, and the (query, key) pairs of its rows
    (chunk rows' causal pairs and one-token rows' contexts together) in
    every attention layer."""
    body = shapes["matmul_params"] - shapes["head_params"]
    return (2.0 * tokens * body + 2.0 * logit_rows * shapes["head_params"]
            + 4.0 * attn_pairs * shapes["q_heads"] * shapes["head_dim"]
            * shapes["attn_layers"]
            + tokens * shapes["gdn_layers"] * costs_gdn.token_flops(shapes))
