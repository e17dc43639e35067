"""Operations and bytes the one-token read of a paged KV cache needs, from
shapes alone, by ``lib/costs.py``'s conventions: a multiply-add is 2 FLOPs,
and these are what the MATHEMATICS requires, not what a path a kernel chose
executes (a zero-padded half of a lane tile, scores against another KV
head's keys that a mask throws away, a whole-pool read are extra work).
``shapes`` is what a family's ``shapes`` returns (``q_heads``, ``kv_heads``,
``head_dim``, and ``attn_layers`` where only some layers keep keys and
values; ``layers`` otherwise).

* A cached token keeps ``2 x kv_heads x head_dim`` values a layer (keys and
  values; 2,048 B in bf16 at 8 KV heads of 64).
* A one-token row's read moves every row of the table blocks it holds once
  (a block is the unit a table names: the rows past the position it feeds
  are moved too, as ``lib/costs_mla.py`` counts them), and a (query, key)
  pair costs ``q_heads x head_dim x 2 x 2`` FLOP (the score and the
  weighted value).
"""

from __future__ import annotations

from typing import Dict, Tuple

VALUE_BYTES = 2         # bf16 keys and values


def kv_layers(shapes: Dict[str, int]) -> int:
    """Layers that keep keys and values."""
    return int(shapes.get("attn_layers", shapes["layers"]))


def token_bytes_a_layer(shapes: Dict[str, int]) -> int:
    """Bytes a cached token keeps in ONE attention layer."""
    return 2 * shapes["kv_heads"] * shapes["head_dim"] * VALUE_BYTES


def decode_read_costs(shapes: Dict[str, int], read_blocks: int,
                      block_size: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the one-token read of ALL attention layers needs for
    rows that hold ``read_blocks`` table blocks between them up to their
    positions: every row of those blocks moved once."""
    keys = read_blocks * block_size
    pair = shapes["q_heads"] * shapes["head_dim"] * 2 * 2
    n = kv_layers(shapes)
    return (float(n * keys * pair),
            float(n * keys * token_bytes_a_layer(shapes)))
