"""Operations and bytes the recurrence of a Mamba-2 layer needs, from
shapes alone, by ``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs,
an ``exp`` counts as one, and these are the operations and bytes the
RECURRENCE requires on one chip (one token after another), not what a
kernel chose to execute: a lower bound for ANY implementation, which the
chunked (matmul) form only exceeds.  ``shapes`` is what
``families/granite_moe_hybrid.py::shapes`` returns (``ssd_layers``,
``ssd_heads`` H, ``ssd_head_dim`` P, ``ssd_state`` N; ``Di = H P``).

Per token, channel and state index, with the state ``s [N, Di]``::

    s = decay * s          1 multiply     (the decay is ONE value a head)
    s += B * (dt x)        1 multiply-add
    y += s * C             1 multiply-add

5 FLOPs a state element, plus ``dt x`` once a channel and ``dt A`` and its
``exp`` once a head.  Everything the recurrence touches is float32: 4 bytes
a value.
"""

from __future__ import annotations

from typing import Dict, Tuple

STATE_BYTES = 4         # float32 state
ROW_BYTES = 4           # dt, x, y, B, C as the recurrence receives them


def channels(shapes: Dict[str, int]) -> int:
    return shapes["ssd_heads"] * shapes["ssd_head_dim"]


def state_bytes(shapes: Dict[str, int]) -> int:
    """One sequence's state in ONE layer."""
    return channels(shapes) * shapes["ssd_state"] * STATE_BYTES


def token_flops(shapes: Dict[str, int]) -> float:
    """FLOPs one token needs in ONE layer."""
    di, n, h = channels(shapes), shapes["ssd_state"], shapes["ssd_heads"]
    return float(5 * di * n + di + 2 * h)


def token_row_bytes(shapes: Dict[str, int]) -> int:
    """Bytes of one token's ``x`` (read), ``y`` (written), ``dt`` a head,
    ``B`` and ``C`` (read) in ONE layer."""
    return (2 * channels(shapes) + shapes["ssd_heads"]
            + 2 * shapes["ssd_state"]) * ROW_BYTES


def step_costs(shapes: Dict[str, int], seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode update of ALL Mamba-2 layers needs for
    ``seqs`` live sequences, one token each: every live slot's state read
    once and written once, plus the token's own rows."""
    layers = shapes["ssd_layers"]
    return (layers * seqs * token_flops(shapes),
            float(layers * seqs * (2 * state_bytes(shapes)
                                   + token_row_bytes(shapes))))


def chunk_costs(shapes: Dict[str, int], tokens: int,
                seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the recurrence of ALL Mamba-2 layers needs for
    ``tokens`` prompt tokens in the chunks of ``seqs`` sequences of one
    batch: each sequence's state read once and written once a batch,
    whatever the chunk's length."""
    layers = shapes["ssd_layers"]
    return (layers * tokens * token_flops(shapes),
            float(layers * (seqs * 2 * state_bytes(shapes)
                            + tokens * token_row_bytes(shapes))))
