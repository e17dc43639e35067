"""Operations and bytes the gated delta rule needs, from shapes alone, by
``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs, and these are the
operations and bytes the RECURRENCE requires (one token after another), not
what the chunked form a kernel chose executes (its triangular inverse and
intra-chunk attention are extra work it does to use the MXU).  ``shapes`` is
what ``families/qwen3_next.py::shapes`` returns (``gdn_layers``,
``gdn_value_heads``, ``gdn_key_dim``, ``gdn_value_dim``).

Per token and value head, with the state ``S [dk, dv]``::

    S *= exp(g)          dk dv multiplies
    S^T k                dk dv multiply-adds
    d = (v - .) * beta   2 dv
    S += k d^T           dk dv multiply-adds
    o = S^T q            dk dv multiply-adds

Everything the rule touches is float32: 4 bytes a value.
"""

from __future__ import annotations

from typing import Dict, Tuple

STATE_BYTES = 4         # float32 recurrent state
ROW_BYTES = 4           # q, k, v, o, g, beta as the rule receives them


def state_matrix_bytes(shapes: Dict[str, int]) -> int:
    """One sequence's recurrent matrices in ONE layer."""
    return shapes["gdn_value_heads"] * shapes["gdn_key_dim"] \
        * shapes["gdn_value_dim"] * STATE_BYTES


def token_flops(shapes: Dict[str, int]) -> float:
    """FLOPs one token needs in ONE layer, all value heads."""
    dk, dv = shapes["gdn_key_dim"], shapes["gdn_value_dim"]
    return float(shapes["gdn_value_heads"] * (7 * dk * dv + 2 * dv))


def token_row_bytes(shapes: Dict[str, int]) -> int:
    """Bytes of one token's q, k (read), v (read), o (written), g and
    beta in ONE layer, all value heads."""
    dk, dv = shapes["gdn_key_dim"], shapes["gdn_value_dim"]
    return shapes["gdn_value_heads"] * (2 * dk + 2 * dv + 2) * ROW_BYTES


def step_costs(shapes: Dict[str, int], seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode update of ALL DeltaNet layers needs for
    ``seqs`` live sequences, one token each: every live slot's matrices
    read once and written once, plus the token's own rows."""
    layers = shapes["gdn_layers"]
    return (layers * seqs * token_flops(shapes),
            float(layers * seqs * (2 * state_matrix_bytes(shapes)
                                   + token_row_bytes(shapes))))


def chunk_costs(shapes: Dict[str, int], tokens: int,
                seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the rule of ALL DeltaNet layers needs for ``tokens``
    prompt tokens in the chunks of ``seqs`` sequences of one batch: each
    sequence's state read once and written once a batch, whatever the
    chunk's length."""
    layers = shapes["gdn_layers"]
    return (layers * tokens * token_flops(shapes),
            float(layers * (seqs * 2 * state_matrix_bytes(shapes)
                            + tokens * token_row_bytes(shapes))))
