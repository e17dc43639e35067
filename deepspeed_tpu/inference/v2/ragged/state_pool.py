"""Per-sequence recurrent state beside the paged KV pool.

A linear-attention (Gated DeltaNet) or state-space (Mamba) layer keeps no
keys and values: it keeps, for every live sequence, a fixed-size state (a
recurrent matrix per head or a scan state per channel, and the tail of its
causal convolution; leaves of any shape and dtype, e.g. a float32 ``[16,
5120]`` beside a bf16 ``[3 x 5120]``).  The state manager holds those in SLOTS:
``num_slots`` of them, one taken when a sequence is created and released when
it is flushed, plus one scratch slot (index ``num_slots``) that pad rows of a
step program read and write so that they touch no live sequence.

The device arrays (``{layer_<i>: {leaf: [num_slots + 1, ...]}}``) live in the
same cache tree as the KV pools, so one donation and one ``update`` cover
both; this class owns the geometry and the host's free list.  A slot is not
cleared when it is released: the step program zeroes the state of a sequence
whose chunk starts at position 0, on the device, so a recompute (preemption,
a failed step) restarts from zero whatever the slot held.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np


#: what a cache with state slots cannot serve (``kv_cache.FEATURES``) and why:
#: each skips or rewinds positions, which the state cannot follow
STATE_SLOTS = ("keeps per-sequence recurrent state (state_spec)", {
    "prefix_cache": "attach_prefix and its copy-on-write fork skip the "
                    "prefill of cached positions, which the state would "
                    "never reach (no snapshots at block boundaries)",
    "host_tier": "a restored block skips the prefill of its positions as a "
                 "prefix-cache hit does, with no state snapshot beside it",
    "kv_handoff": "the payload carries KV rows only and skips the positions "
                  "the state has to be recomputed over; without it the "
                  "sequence is recomputed from a zeroed slot",
    "verify": "rejected lookahead tokens would have advanced the state and "
              "cannot be rolled back",
    "decode_loop": "the scanned program does not carry state slots; "
                   "decode_step does",
})


#: lanes of the chip's tiles: an array's minor dimension is stored in
#: whole tiles of this many values
LANES = 128


def slot_bytes(shape: Sequence[int], dtype) -> int:
    """HBM bytes ONE slot of a leaf holds: its values with the minor
    dimension rounded up to whole lane tiles, as the chip stores it.  Every
    leaf whose rows are whole tiles counts as its values, which is every
    leaf the models declare at their published widths since PR 58 (a
    float32 ``[30, 96, 192]`` delta-rule state held 256 lanes a row, a
    third more than its 2,211,840 B, until
    ``ops/gated_delta_rule.py::state_leaf_shape`` stored it as ``[15, 96,
    384]``); a tiny test width still pads.  The few rows a pool's slot axis
    is padded by are not in it."""
    *rows, lanes = shape
    return int(np.prod(rows, dtype=np.int64)) * -(-lanes // LANES) * LANES \
        * jnp.dtype(dtype).itemsize


class StateSlotPool:
    def __init__(self, num_slots: int, layers: Sequence[int],
                 leaves: Dict[str, Tuple[Tuple[int, ...], Any]]):
        self.num_slots = int(num_slots)
        self.layers = tuple(layers)
        self.leaves = dict(leaves)
        self._free: List[int] = list(range(self.num_slots - 1, -1, -1))

    @property
    def scratch(self) -> int:
        """The slot pad rows use."""
        return self.num_slots

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def held(self) -> int:
        return self.num_slots - len(self._free)

    def take(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"no free state slot ({self.num_slots} held): a model with "
                f"recurrent state tracks at most max_ragged_sequence_count "
                f"sequences")
        return self._free.pop()

    def release(self, slot: int) -> None:
        self._free.append(slot)

    def new_arrays(self) -> Dict[str, Dict[str, Any]]:
        return {f"layer_{i}": {
            name: jnp.zeros((self.num_slots + 1,) + tuple(shape), dtype)
            for name, (shape, dtype) in self.leaves.items()}
            for i in self.layers}

    @property
    def per_sequence_bytes(self) -> int:
        """HBM bytes one live sequence holds across every stateful layer,
        whatever its length (the KV pool's ``per_token_bytes`` is apart):
        what the chip holds, lane padding included (:func:`slot_bytes`)."""
        return len(self.layers) * sum(
            slot_bytes(shape, dtype) for shape, dtype in self.leaves.values())

    @property
    def held_bytes(self) -> int:
        """What the live sequences' slots hold."""
        return self.held * self.per_sequence_bytes

    @property
    def total_bytes(self) -> int:
        """The device arrays: every slot and the scratch one."""
        return (self.num_slots + 1) * self.per_sequence_bytes
