"""Operations and bytes the routed-expert FFN needs, from shapes alone, by
``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs; these are the
operations and bytes the mathematics requires for the rows that were
routed, not what a kernel chose to execute or to move (a grouped GEMM that
multiplies whole 256-row tiles of which four rows are live has executed
64 times what is counted here).  ``shapes`` is what
``families/olmoe.py::shapes`` returns (``experts``, ``experts_per_token``,
``expert_width``).
"""

from __future__ import annotations

from typing import Dict, Tuple

#: below this many routed rows a tick is left out of ``gmm_roofline_pct``:
#: ``min(experts, rows)`` then over-counts the experts that are touched by
#: far more than the ~2% it does from 256 rows up (64 experts at top-8,
#: near-uniform routing: 32 tokens touch 63.1 of 64)
MIN_ROUTED_ROWS = 256


def routed_rows(shapes: Dict[str, int], tokens: int) -> int:
    """Rows of the grouped GEMMs for ``tokens`` real tokens: each is routed
    to ``experts_per_token`` experts."""
    return int(tokens) * int(shapes["experts_per_token"])


def experts_touched_at_most(shapes: Dict[str, int], rows: int) -> int:
    """Upper bound on the experts whose weights a tick must read: every
    routed row could go to another expert."""
    return min(int(shapes["experts"]), int(rows))


def grouped_ffn_costs(shapes: Dict[str, int], rows: int,
                      dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the three grouped GEMMs (gate, up, down) of ALL
    layers need for ``rows`` routed rows.  FLOPs: 3 GEMMs x 2 x rows x
    hidden x expert_width a layer.  Bytes a layer: the three matrices of at
    most ``min(experts, rows)`` experts, each read once, plus each GEMM's
    own rows in and out (gate and up read ``hidden`` and write
    ``expert_width`` values a row, down the reverse)."""
    h, f, layers = shapes["hidden"], shapes["expert_width"], shapes["layers"]
    flops = layers * 3 * 2.0 * rows * h * f
    weights = experts_touched_at_most(shapes, rows) * 3 * h * f
    acts = 3 * rows * (h + f)
    return flops, float(layers * (weights + acts) * dtype_bytes)
