"""Operations and bytes the attention reads of a model with window and
global layers need, from shapes alone, by ``lib/costs.py``'s conventions: a
multiply-add is 2 FLOPs, and these are what the MATHEMATICS requires, not
what a path a kernel chose executes (a block of which one key is inside the
band, a tile whose first rows see further back than its last, scores a mask
throws away are extra work).  ``shapes`` is what ``families/afmoe.py::shapes``
returns (``q_heads``, ``kv_heads``, ``head_dim``, ``window``,
``window_layers``, ``full_layers``).

* A query at position ``t`` sees key ``j`` iff ``j <= t`` in a global layer
  and iff ``t - window < j <= t`` in a window layer: ``t + 1`` and ``min(t +
  1, window)`` keys.
* A (query, key) pair costs ``q_heads x head_dim x 2 x 2`` FLOP a layer (the
  score and the weighted value).
* A one-token row's read moves every row of the table blocks it reads once
  (a block is the unit a table names, as ``lib/costs_paged.py`` counts): in a
  global layer the blocks it holds up to its position, in a window layer
  those of them that hold a key inside the band.
"""

from __future__ import annotations

from typing import Dict, Tuple

from benchmark.lib import costs_paged


def pair_flops(shapes: Dict[str, int]) -> int:
    """FLOPs one visible (query, key) pair costs in ONE layer."""
    return shapes["q_heads"] * shapes["head_dim"] * 2 * 2


def causal_pairs(start: int, tokens: int) -> int:
    """Visible pairs of a chunk of ``tokens`` queries from position
    ``start`` in a global layer: ``sum(t + 1)``."""
    return tokens * (2 * start + tokens + 1) // 2


def banded_pairs(start: int, tokens: int, window: int) -> int:
    """The same in a window layer: ``sum(min(t + 1, window))``."""
    ramp = max(0, min(start + tokens, window - 1) - start)   # t + 1 < window
    return causal_pairs(start, ramp) + (tokens - ramp) * window


def band_blocks(pos: int, window: int, block_size: int) -> int:
    """Table blocks that hold a key a query at ``pos`` sees in a window
    layer."""
    return pos // block_size - max(0, pos - window + 1) // block_size + 1


def walk_costs(shapes: Dict[str, int], read_blocks: int,
               read_blocks_win: int, block_size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the one-token reads of ALL attention layers need:
    ``read_blocks`` table blocks in each global layer, ``read_blocks_win``
    in-band blocks summed over the window layers (the program's counters of
    those names)."""
    keys = (shapes["full_layers"] * read_blocks + read_blocks_win) \
        * block_size
    return (float(keys * pair_flops(shapes)),
            float(keys * costs_paged.token_bytes_a_layer(shapes)))


def chunk_costs(shapes: Dict[str, int], pairs_full: int,
                pairs_win: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the chunk reads of ALL attention layers need for
    ``pairs_full`` causal pairs in each global layer and ``pairs_win``
    banded pairs in each window layer (the program's ``attn_pairs`` /
    ``attn_pairs_win``).  Bytes are left at 0: a chunk's read is bound by
    its FLOPs at every length the cells serve (4 x 48 x 128 FLOP a pair
    against 4,096 B a key shared by a tile's 128 queries)."""
    return (float(pair_flops(shapes) * (shapes["full_layers"] * pairs_full
                                        + shapes["window_layers"]
                                        * pairs_win)), 0.0)
