"""Operations and bytes latent attention (MLA) needs, from shapes alone, by
``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs, and these are what
the MATHEMATICS requires, not what a path a kernel chose executes (the
absorbed form's wider dots, a row's lane padding, a context expanded once a
tile instead of once a chunk are extra work).  ``shapes`` is what
``families/moonlight.py::shapes`` returns (``layers``, ``q_heads``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``).

* A cached token keeps ``kv_lora_rank + qk_rope_head_dim`` values a layer
  (576 at the published widths: 1,152 B in bf16).
* A one-token row's read moves every row of the blocks it holds once, and
  in the absorbed form a (query, key) pair costs ``H x ((rank + rope) +
  rank) x 2`` FLOP (scores against the row, probabilities times its latent
  part).
* A prompt chunk's read in the expanded form costs ``H x ((nope + rope) +
  v) x 2`` FLOP a causal (query, key) pair.
* Expanding a context row costs ``rank x H x (nope + v) x 2`` FLOP, reads
  the row and writes ``H x (nope + v)`` values.
"""

from __future__ import annotations

from typing import Dict, Tuple

VALUE_BYTES = 2         # bf16 rows, keys and values


def row_values(shapes: Dict[str, int]) -> int:
    """Values a cached token keeps in ONE layer."""
    return shapes["kv_lora_rank"] + shapes["qk_rope_head_dim"]


def decode_read_costs(shapes: Dict[str, int], read_blocks: int,
                      block_size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the absorbed read of ALL layers needs for one-token
    rows that hold ``read_blocks`` table blocks between them up to their
    positions: every row of those blocks moved once."""
    keys = read_blocks * block_size
    row = row_values(shapes)
    pair = shapes["q_heads"] * (row + shapes["kv_lora_rank"]) * 2
    return (float(shapes["layers"] * keys * pair),
            float(shapes["layers"] * keys * row * VALUE_BYTES))


def prefill_read_costs(shapes: Dict[str, int],
                       attn_pairs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the expanded read of ALL layers needs for
    ``attn_pairs`` causal (query, key) pairs.  Bytes are left at zero: what
    it reads was made by the expansion on the chip, and how often a tile
    re-reads it is the kernel's choice."""
    pair = shapes["q_heads"] * (shapes["qk_nope_head_dim"]
                                + shapes["qk_rope_head_dim"]
                                + shapes["v_head_dim"]) * 2
    return float(shapes["layers"] * attn_pairs * pair), 0.0


def expand_costs(shapes: Dict[str, int],
                 ctx_rows: int) -> Tuple[float, float]:
    """(FLOPs, bytes) expanding ``ctx_rows`` context rows needs in ALL
    layers: each row read once, its keys and values written once."""
    out = shapes["q_heads"] * (shapes["qk_nope_head_dim"]
                               + shapes["v_head_dim"])
    return (float(shapes["layers"] * ctx_rows
                  * shapes["kv_lora_rank"] * out * 2),
            float(shapes["layers"] * ctx_rows
                  * (row_values(shapes) + out) * VALUE_BYTES))
