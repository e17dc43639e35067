"""Operations and bytes learned sparse attention (DeepSeek Sparse Attention:
an indexer over a narrow cached row, then a read of the selected latent rows
alone) needs, from shapes and the program's own counters, by ``lib/costs.py``'s
conventions: a multiply-add is 2 FLOPs, and these are what the MATHEMATICS
requires, not what a path executes (a masked read that multiplies unselected
pairs, the absorbed form's wider dots, a row's lane padding, a walk to the end
of a step are extra work, so no path reads over 100).  ``shapes`` is what
``families/glm_moe_dsa.py::shapes`` returns.

* The indexer key a token keeps is ``index_head_dim`` values a layer (128:
  256 B in bf16); a score costs ``index_heads x index_head_dim x 2`` FLOP.
* A selected latent row is ``kv_lora_rank + qk_rope_head_dim`` values (576:
  1,152 B); a selected (query, key) pair costs ``H x ((nope + rope) + v) x
  2`` FLOP in the expanded form, the cheaper of the two.
* **One-token rows are bytes**: ``idx_keys`` positions scored, each key
  moved once, and ``sel_keys`` rows read once, with their FLOPs.
* **Tile rows are FLOPs**: ``idx_pairs`` scores and ``sel_pairs`` selected
  pairs.  Their bytes are left at zero: a chunk's rows share what they read,
  and how often a block is re-read is the path's choice.
"""

from __future__ import annotations

from typing import Dict, Tuple

VALUE_BYTES = 2         # bf16 rows


def index_costs(shapes: Dict[str, int], idx_keys: int,
                idx_pairs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the index scores of ALL layers need: ``idx_keys``
    positions scored by one-token rows, ``idx_pairs`` by tile rows."""
    score = shapes["index_heads"] * shapes["index_head_dim"] * 2
    return (float(shapes["layers"] * (idx_keys + idx_pairs) * score),
            float(shapes["layers"] * idx_keys * shapes["index_head_dim"]
                  * VALUE_BYTES))


def read_costs(shapes: Dict[str, int], sel_keys: int,
               sel_pairs: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the read of the selected rows needs in ALL layers:
    ``sel_keys`` rows read by one-token rows, ``sel_pairs`` selected pairs
    of tile rows."""
    row = shapes["kv_lora_rank"] + shapes["qk_rope_head_dim"]
    pair = shapes["q_heads"] * (shapes["qk_nope_head_dim"]
                                + shapes["qk_rope_head_dim"]
                                + shapes["v_head_dim"]) * 2
    return (float(shapes["layers"] * (sel_keys + sel_pairs) * pair),
            float(shapes["layers"] * sel_keys * row * VALUE_BYTES))


def selected_pct(idx: int, sel: int):
    """Positions read over positions scored, in percent (None: none
    scored)."""
    return 100.0 * sel / idx if idx else None
