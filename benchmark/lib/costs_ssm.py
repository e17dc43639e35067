"""Operations and bytes the selective scan of a Mamba layer needs, from
shapes alone, by ``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs,
an ``exp`` counts as one, and these are the operations and bytes the
RECURRENCE requires on one chip (one token after another), not what a
kernel chose to execute.  ``shapes`` is what ``families/jamba.py::shapes``
returns (``ssm_layers``, ``ssm_channels`` Di, ``ssm_state`` N).

Per token, channel and state index, with the state ``s [Di, N]``::

    exp(dt * A)            1 multiply + 1 exp
    s = decay * s + . * B  1 multiply-add + 1 multiply (dt x by B)
    y += s * C             1 multiply-add

7 FLOPs a state element, plus ``dt * x`` once a channel.  Everything the
scan touches is float32: 4 bytes a value.
"""

from __future__ import annotations

from typing import Dict, Tuple

STATE_BYTES = 4         # float32 scan state
ROW_BYTES = 4           # dt, dt x, y, B, C as the scan receives them


def state_bytes(shapes: Dict[str, int]) -> int:
    """One sequence's scan state in ONE layer."""
    return shapes["ssm_channels"] * shapes["ssm_state"] * STATE_BYTES


def token_flops(shapes: Dict[str, int]) -> float:
    """FLOPs one token needs in ONE layer."""
    di, n = shapes["ssm_channels"], shapes["ssm_state"]
    return float(7 * di * n + di)


def token_row_bytes(shapes: Dict[str, int]) -> int:
    """Bytes of one token's ``dt`` and ``dt x`` (read), ``y`` (written),
    ``B`` and ``C`` (read) in ONE layer."""
    return (3 * shapes["ssm_channels"] + 2 * shapes["ssm_state"]) * ROW_BYTES


def step_costs(shapes: Dict[str, int], seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode update of ALL Mamba layers needs for
    ``seqs`` live sequences, one token each: every live slot's state read
    once and written once, plus the token's own rows."""
    layers = shapes["ssm_layers"]
    return (layers * seqs * token_flops(shapes),
            float(layers * seqs * (2 * state_bytes(shapes)
                                   + token_row_bytes(shapes))))


def chunk_costs(shapes: Dict[str, int], tokens: int,
                seqs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the scan of ALL Mamba layers needs for ``tokens``
    prompt tokens in the chunks of ``seqs`` sequences of one batch: each
    sequence's state read once and written once a batch, whatever the
    chunk's length."""
    layers = shapes["ssm_layers"]
    return (layers * tokens * token_flops(shapes),
            float(layers * (seqs * 2 * state_bytes(shapes)
                            + tokens * token_row_bytes(shapes))))
