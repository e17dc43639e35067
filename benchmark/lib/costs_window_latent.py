"""Operations and bytes the reads of a WINDOW layer whose cache row is a
latent need (dots3-note's sliding layers: one row ``[c | k_pe]`` a token for
all heads, seen through a band of ``window`` positions), by ``lib/costs.py``'s
conventions: a multiply-add is 2 FLOPs, and these are what the MATHEMATICS
requires, not what a path executes (a block of which one key is inside the
band, a row's lane padding, the absorbed form's wider dots over a tile,
scores a mask throws away are extra work, so no path reads over 100).
``shapes`` is what ``families/dots3_note.py::shapes`` returns
(``window_latent_layers``, ``window``, ``swa_q_heads``, ``swa_kv_lora_rank``,
``swa_qk_nope_head_dim``, ``swa_qk_rope_head_dim``, ``swa_v_head_dim``).

* A query at position ``t`` sees ``min(t, window - 1) + 1`` keys.  A key is a
  row of ``rank + rope`` values at its REAL width (1,088: 2,176 B in bf16;
  the pool pads it to 1,152 lanes, which a walk at the HBM peak pays for: it
  reads 94, not 100).
* **A one-token row is bytes**: each visible key's row moved once a layer,
  with the absorbed form's FLOPs beside them (``H x (row + rank) x 2`` a
  key: the only form a single query has; 124 FLOP a byte against the
  chip's 240).
* **A chunk's rows are FLOPs**, in the cheaper EXPANDED form: the band's
  rows expanded once a chunk (``rank x H x (nope + v) x 2`` a row) and ``H x
  (nope + rope + v) x 2`` a visible (query, key) pair.  Bytes are left at
  zero: a chunk's rows share what they read.

The program's counters (``engine/build_batch`` / ``engine/decode_prep``)
are already summed over the window layers where their name ends in ``_win``
and counts keys or blocks (``read_keys_win``); ``attn_pairs_win`` and
``ctx_rows_win`` are a LAYER's and are multiplied here.
"""

from __future__ import annotations

from typing import Dict, Tuple

VALUE_BYTES = 2         # bf16 rows


def band_keys(pos: int, window: int) -> int:
    """Keys a query at ``pos`` sees in a window layer."""
    return min(pos, window - 1) + 1


def band_rows(start: int, tokens: int, window: int) -> int:
    """Cached rows a chunk of ``tokens`` queries from ``start`` sees at all:
    its own and the ``window - 1`` before its first."""
    return min(start, window - 1) + tokens


def row_values(shapes: Dict[str, int]) -> int:
    """Values of a cached row's content: latent and rotated key."""
    return shapes["swa_kv_lora_rank"] + shapes["swa_qk_rope_head_dim"]


def walk_costs(shapes: Dict[str, int],
               read_keys_win: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the one-token reads of ALL window layers need for
    ``read_keys_win`` visible keys (summed over rows and layers)."""
    row = row_values(shapes)
    pair = shapes["swa_q_heads"] * (row + shapes["swa_kv_lora_rank"]) * 2
    return (float(read_keys_win * pair),
            float(read_keys_win * row * VALUE_BYTES))


def chunk_costs(shapes: Dict[str, int], attn_pairs_win: int,
                ctx_rows_win: int) -> Tuple[float, float]:
    """(FLOPs, 0) the chunk reads of ALL window layers need for
    ``attn_pairs_win`` banded pairs and ``ctx_rows_win`` band rows a
    layer."""
    h = shapes["swa_q_heads"]
    nope, rope, vd = shapes["swa_qk_nope_head_dim"], \
        shapes["swa_qk_rope_head_dim"], shapes["swa_v_head_dim"]
    pair = h * (nope + rope + vd) * 2
    expand = shapes["swa_kv_lora_rank"] * h * (nope + vd) * 2
    return (float(shapes["window_latent_layers"]
                  * (attn_pairs_win * pair + ctx_rows_win * expand)), 0.0)
