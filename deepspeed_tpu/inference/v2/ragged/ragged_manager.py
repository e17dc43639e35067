"""Sequence/KV state manager (reference: inference/v2/ragged/ragged_manager.py
``DSStateManager`` — tracks live sequences and owns the blocked KV cache).

Host-side bookkeeping only: which sequences are live, how many KV blocks each
owns, and whether a proposed ragged batch fits the cache.  All device state
lives in :class:`BlockedKVCache` and is threaded functionally through the
jitted forward by the engine.

With ``kv_cache.enable_prefix_cache`` the manager also owns a
:class:`RadixPrefixCache`: new sequences attach to warm KV blocks covering
their longest cached token prefix (:meth:`attach_prefix`), full blocks are
registered back into the tree as prefill/decode advances
(:meth:`register_prefix`), and allocation evicts cold cache entries under
KV pressure — ``free_blocks`` counts evictable warm blocks as free, so the
scheduler's admission view stays truthful.

**Two kinds of KV layer** (a model's ``kv_groups``: ``{"window": {"layers":
[...], "window": W}}``; every other KV layer is global; with ``"row":
{leaf: lanes}`` the window layers keep that row and the global ones the
model's ``kv_row``: a row a group, ``kv_cache.py``).  The global group is
what the manager always had: ``kv_config.num_blocks`` blocks, ``allocator``,
``seq.blocks``, ``free_blocks`` (the pool that binds admission).  The window
group has a pool, an allocator (``win_allocator``) and a table a sequence
(``seq.win_blocks`` from entry ``seq.win_first``) of its own.  A key at
position ``j`` is visible to a query at ``t`` iff ``t - W < j <= t``, so once
a sequence has ``seen_tokens`` positions cached every block wholly below
``seen_tokens - W + 1`` is dead to it, and :meth:`release_window` returns
those.  The engine releases every tracked sequence's (:meth:`release_windows`)
where it builds the next batch, before it allocates for the chunks: a program
already dispatched carries its own tables and the cache is threaded through
the programs in order, so a released block is rewritten only
by a later program.  **The pool's size** comes from what the manager knows,
with no knob (:attr:`window_pool_blocks`).  Released as of its own
``seen_tokens`` and then given ``n`` more tokens a sequence holds ``ceil((seen
+ n) / bs) - (seen - W + 1) // bs <= (W - 1 + n) // bs + 2`` blocks, and one
forward's ``n`` sum to its token budget at most; with ``W - 1 = q x bs + r``
that is ``max_ragged_sequence_count x (q + 2) + (max_ragged_sequence_count x
r + budget) // bs`` blocks (+ the trash block) for the tables of as many
sequences as one forward has rows, whatever ``max_context`` is.  That is what
a second allocator buys over a fixed ring of blocks a sequence slot, which
would need each slot's own worst case, the band AND a whole budget's chunk
(:attr:`window_table_bound`: 41 blocks a sequence where the sum gives 1,095
for 32 at W = 4,096, a budget of 1,024 and blocks of 128: 34.2 each).
More sequences than that can be tracked, so admission still
counts (:meth:`window_blocks_needed`).

**A cache per (layer, pass)** (a model's ``kv_passes``: its stack of layers
runs that many times a token, each pass over keys and values of its own).
Nothing of the bookkeeping here changes: one allocator, one table a sequence,
and a block id that names the same offset in every pass's part of a pool
(``kv_cache.py``, the module doc); a block is ``passes`` times the bytes, which
``per_token_bytes`` and the occupancy gauges count.

**What a layout cannot serve** is said beside each extension's definition
(``state_pool.STATE_SLOTS``, ``kv_cache.LATENT_ROW`` / ``WINDOW_GROUP`` /
``PASS_CACHES``),
merged into :attr:`DSStateManager.unserved` and checked in ONE place,
:meth:`DSStateManager.require`, by every path behind a feature.
"""

from __future__ import annotations

import logging
import time
from typing import Dict, Iterable, List, Optional, Sequence

from deepspeed_tpu.inference.v2.config_v2 import (DSStateManagerConfig,
                                                  KVCacheConfig)
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.host_tier import HostKVTier
from deepspeed_tpu.inference.v2.ragged.kv_cache import (FEATURES,
                                                        LATENT_ROW,
                                                        PASS_CACHES,
                                                        WINDOW_GROUP,
                                                        BlockedKVCache,
                                                        CacheLayoutError,
                                                        is_int8)
from deepspeed_tpu.inference.v2.ragged.prefix_cache import RadixPrefixCache
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    DSSequenceDescriptor,
)
from deepspeed_tpu.inference.v2.ragged.state_pool import (STATE_SLOTS,
                                                          StateSlotPool)


class DSStateManager:
    """reference ragged_manager.py:DSStateManager."""

    def __init__(self, config: DSStateManagerConfig,
                 kv_config: KVCacheConfig,
                 num_layers: int, num_kv_heads: int, head_dim: int,
                 dtype=None, state_spec=None, kv_row=None, kv_groups=None,
                 kv_passes: int = 1):
        self.config = config
        self.kv_config = kv_config
        self.block_size = kv_config.block_size
        num_blocks = kv_config.num_blocks
        if num_blocks is None:
            # enough for max_ragged_sequence_count sequences at max_context,
            # +1 for the trash block
            per_seq = -(-config.max_context // self.block_size)
            num_blocks = config.max_ragged_sequence_count * per_seq + 1
        self.allocator = BlockedAllocator(num_blocks)
        #: feature -> why this layout cannot serve it (the module doc)
        self.unserved: Dict[str, str] = {}
        win_row = (kv_groups or {}).get("window", {}).get("row")
        for keeps, cannot in [table for stated, table in (
                (state_spec is not None, STATE_SLOTS),
                (kv_row or win_row, LATENT_ROW),
                (kv_groups is not None, WINDOW_GROUP),
                (kv_passes > 1, PASS_CACHES)) if stated]:
            for feature, why in cannot.items():
                prior = self.unserved.get(feature)
                self.unserved[feature] = (f"{prior} and " if prior else "") \
                    + f"{keeps}; {why}"
        kwargs = {}
        # precedence: explicit kv_cache.dtype string > legacy cache_dtype
        # > the model's compute dtype
        if getattr(kv_config, "dtype", None) is not None:
            kwargs["dtype"] = kv_config.dtype
        elif dtype is not None or kv_config.cache_dtype is not None:
            kwargs["dtype"] = kv_config.cache_dtype or dtype
        if "dtype" in kwargs and is_int8(kwargs["dtype"]):
            self.require("int8_kv", "kv_cache.dtype=int8")
        if getattr(kv_config, "host_tier", False):
            self.require("host_tier", "kv_cache.host_tier (behind "
                         "kv_cache.enable_prefix_cache)")
        if getattr(kv_config, "enable_prefix_cache", False):
            self.require("prefix_cache", "kv_cache.enable_prefix_cache")
        #: slots of per-sequence recurrent state, for a model whose
        #: ``state_spec`` names layers that keep such state instead of keys
        #: and values (``{"layers": [...], "leaves": {name: (shape,
        #: dtype)}}``): as many as sequences one forward can hold
        self.state_pool: Optional[StateSlotPool] = None
        if kv_row:          # the model states its pool row (latent attention)
            kwargs["kv_row"] = kv_row
        if kv_passes > 1:   # one cache per (layer, pass): the module doc
            if kv_groups is not None:
                raise CacheLayoutError(
                    "kv_passes beside kv_groups: a pass's offset would "
                    "differ between the window pool and the global one")
            kwargs["passes"] = kv_passes
        if state_spec is not None:
            self.state_pool = StateSlotPool(
                config.max_ragged_sequence_count, state_spec["layers"],
                state_spec["leaves"])
            kwargs["kv_layers"] = [i for i in range(num_layers)
                                   if i not in set(state_spec["layers"])]
        #: the window group (the module doc): its width in tokens, its
        #: allocator and the blocks it has released so far; None / 0
        #: without ``kv_groups``
        self.window: Optional[int] = None
        self.win_allocator: Optional[BlockedAllocator] = None
        self.win_released = 0
        if kv_groups is not None:
            win = kv_groups["window"]
            self.window = int(win["window"])
            self.win_allocator = BlockedAllocator(self.window_pool_blocks + 1)
            kwargs["window_layers"] = win["layers"]
            kwargs["window_blocks"] = self.win_allocator.num_blocks
            if win_row:     # the group's own row: a row a group
                kwargs["window_row"] = win_row
        self.kv_cache = BlockedKVCache(num_layers, num_blocks, self.block_size,
                                       num_kv_heads, head_dim, **kwargs)
        if self.state_pool is not None:
            self.kv_cache.cache.update(self.state_pool.new_arrays())
        self.prefix_cache: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.allocator, self.block_size)
            if getattr(kv_config, "enable_prefix_cache", False) else None)
        self.host_tier: Optional[HostKVTier] = None
        if getattr(kv_config, "host_tier", False):
            if self.prefix_cache is None:
                raise ValueError(
                    "kv_cache.host_tier requires enable_prefix_cache — "
                    "cold blocks spool from the radix tree's LRU "
                    "eviction path")
            tier_bytes = getattr(kv_config, "host_tier_bytes", None)
            if tier_bytes is None:
                from deepspeed_tpu.utils.logging import log_dist

                log_dist(
                    "kv_cache.host_tier with host_tier_bytes unset: "
                    "every LRU-evicted block spools to host RAM and "
                    "stays until resumed — a long-running server with "
                    "non-repeating prompts grows host RSS without "
                    "bound; set kv_cache.host_tier_bytes to cap it",
                    level=logging.WARNING)
            self.host_tier = HostKVTier(max_bytes=tier_bytes)
            self.prefix_cache.spool_fn = self._spool_nodes
        self._seqs: Dict[int, DSSequenceDescriptor] = {}

    def require(self, feature: str, path: str) -> None:
        """Raise :class:`CacheLayoutError` (``path``, the extension, why)
        when this layout cannot serve ``feature`` (``kv_cache.FEATURES``)."""
        assert feature in FEATURES, feature
        if feature in self.unserved:
            raise CacheLayoutError(
                f"{path}: the model {self.unserved[feature]}")

    # ------------------------------------------------------------------ #
    # Sequence tracking (reference get_or_create_sequence / flush)
    # ------------------------------------------------------------------ #
    @property
    def n_tracked_sequences(self) -> int:
        return len(self._seqs)

    @property
    def free_blocks(self) -> int:
        """Schedulable capacity: genuinely free blocks plus warm cache
        blocks nothing but the radix tree still references (allocation
        evicts those on demand)."""
        free = self.allocator.free_blocks
        if self.prefix_cache is not None:
            free += self.prefix_cache.evictable_blocks
        return free

    def get_sequence(self, uid: int) -> Optional[DSSequenceDescriptor]:
        return self._seqs.get(uid)

    def get_or_create_sequence(self, uid: int) -> DSSequenceDescriptor:
        seq = self._seqs.get(uid)
        if seq is None:
            if len(self._seqs) >= self.config.max_tracked_sequences:
                raise RuntimeError(
                    f"too many tracked sequences "
                    f"({self.config.max_tracked_sequences})")
            seq = DSSequenceDescriptor(uid=uid)
            if self.state_pool is not None:
                seq.state_slot = self.state_pool.take()
            self._seqs[uid] = seq
        return seq

    def blocks_needed(self, seq: DSSequenceDescriptor, new_tokens: int) -> int:
        return seq.tokens_needed_capacity(new_tokens, self.block_size)

    # ------------------------------------------------------------------ #
    # The window group (a model with ``kv_groups``; the module doc)
    # ------------------------------------------------------------------ #
    @property
    def window_table_bound(self) -> int:
        """Most blocks a sequence's window table holds at once: those a
        forward's chunk (at most the token budget) writes and its first
        query still sees, ``ceil((W + budget) / block_size) + 1``."""
        return -(-(self.window + self.config.max_ragged_batch_size)
                 // self.block_size) + 1

    @property
    def window_pool_blocks(self) -> int:
        """Most blocks the window tables of ``max_ragged_sequence_count``
        sequences hold together (the module doc): each sequence's band as
        of its own ``seen_tokens``, and one forward's tokens."""
        bs, seqs = self.block_size, self.config.max_ragged_sequence_count
        q, r = divmod(self.window - 1, bs)
        return seqs * (q + 2) + (seqs * r + max(
            self.config.max_ragged_batch_size, seqs)) // bs

    def _window_first(self, seen_tokens: int) -> int:
        """First table entry a query at ``seen_tokens`` or later can see."""
        return max(0, seen_tokens - self.window + 1) // self.block_size

    def window_blocks_needed(self, seq: Optional[DSSequenceDescriptor],
                             new_tokens: int) -> int:
        """Blocks the window allocator must still give for ``new_tokens``
        more of ``seq`` (None: a sequence yet to be created) at the most,
        after what :meth:`release_window` returns of its own: a prompt
        longer than the budget is fed in chunks with a release between
        them, so never more than :attr:`window_table_bound` at once."""
        seen = seq.seen_tokens if seq else 0
        first = self._window_first(seen)
        end = -(-(seen + new_tokens) // self.block_size)
        held = 0
        if seq is not None:
            held = len(seq.win_blocks) - min(max(first - seq.win_first, 0),
                                             len(seq.win_blocks))
        return max(0, min(end - first, self.window_table_bound) - held)

    def release_window(self, seq: DSSequenceDescriptor) -> int:
        """Return the window blocks of ``seq`` that no query at
        ``seq.seen_tokens`` or later can see; how many."""
        n = min(self._window_first(seq.seen_tokens) - seq.win_first,
                len(seq.win_blocks))
        if n <= 0:
            return 0
        self.win_allocator.free(seq.win_blocks[:n])
        del seq.win_blocks[:n]
        seq.win_first += n
        self.win_released += n
        return n

    def release_windows(self) -> int:
        """:meth:`release_window` for every tracked sequence: what the
        pool's size counts on before a forward's chunks are allocated."""
        return sum(self.release_window(s) for s in self._seqs.values())

    def _allocate(self, num_blocks: int) -> List[int]:
        """Allocate, evicting cold prefix-cache entries when the free list
        alone cannot cover the request."""
        short = num_blocks - self.allocator.free_blocks
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
        return self.allocator.allocate(num_blocks)

    def maybe_allocate_kv(self, seq: DSSequenceDescriptor,
                          new_tokens: int) -> None:
        """reference engine_v2.py maybe_allocate_kv: grow the block table."""
        need = self.blocks_needed(seq, new_tokens)
        if need:
            seq.blocks.extend(self._allocate(need))
        if self.window is not None:
            have = seq.win_first + len(seq.win_blocks)
            need = -(-(seq.seen_tokens + new_tokens) // self.block_size) - have
            if need > 0:
                seq.win_blocks.extend(self.win_allocator.allocate(need))

    def flush_sequence(self, uid: int) -> None:
        """reference flush: release a finished sequence's KV blocks.
        Shared (prefix-cached) blocks just drop this sequence's reference
        — the radix tree keeps them warm for the next matching request."""
        seq = self._seqs.pop(uid, None)
        if seq is None:
            raise ValueError(f"unknown sequence uid {uid}")
        if seq.blocks:
            self.allocator.free(seq.blocks)
        if seq.win_blocks:
            self.win_allocator.free(seq.win_blocks)
        if self.state_pool is not None:
            self.state_pool.release(seq.state_slot)

    def flush(self, uids: Iterable[int]) -> None:
        for uid in uids:
            self.flush_sequence(uid)

    # ------------------------------------------------------------------ #
    # Prefix cache (attach on admission, register as KV fills)
    # ------------------------------------------------------------------ #
    def attach_prefix(self, seq: DSSequenceDescriptor,
                      tokens: Sequence[int]) -> int:
        """Attach a FRESH sequence to the warm KV blocks covering its
        longest cached prefix of ``tokens``; returns the number of prompt
        tokens whose prefill is thereby skipped (0 on miss / cache off).

        At least one token is always left to run — the engine must still
        produce last-token logits — so a fully cached prompt attaches
        ``len(tokens) - 1`` positions, copy-on-write forking the final
        block (its last row gets rewritten by the re-run token, and shared
        blocks are never written).
        """
        cache = self.prefix_cache
        if (cache is None or seq.seen_tokens or seq.blocks or seq.pending
                or len(tokens) < 2):
            return 0
        cache.stats.lookups += 1
        blocks = cache.match_blocks(tokens)
        usable = len(tokens) - 1
        # Acquire the match BEFORE anything below can allocate (tier
        # restores, cow fork): the matched blocks are tree-held at
        # refcount 1, and an allocation under pressure evicts exactly
        # such blocks — unprotected, a restore could recycle a block
        # that is already in this match list (same rule the cow path
        # states below).
        self.allocator.acquire(blocks)
        if self.host_tier is not None:
            # extend the in-HBM match through the host cold tier: each
            # tier hit restores a spooled block (bit-exact payload +
            # scales) into a fresh device block and re-enters the tree,
            # already holding the sequence's reference
            blocks = blocks + self._restore_blocks(tokens, len(blocks),
                                                   usable)
        bs = self.block_size
        cached = min(len(blocks) * bs, usable)
        n_keep = -(-cached // bs)
        # match_blocks covers only full blocks of `tokens` and restores
        # stop at ceil(usable/bs), so the match can never exceed n_keep
        # — every acquired reference above is kept
        assert len(blocks) <= n_keep, (len(blocks), n_keep)
        if cached <= 0:
            cache.stats.misses += 1
            return 0
        cow = cached < n_keep * bs
        fresh: Optional[int] = None
        if cow:
            # Allocate the fork target with the match already acquired
            # (refcount >= 2), so eviction under pressure can reclaim cold
            # tree blocks but never the match itself.
            try:
                fresh = self._allocate(1)[0]
            except RuntimeError:
                # no room to fork the trimmed block: drop it from the match
                self.allocator.free([blocks[-1]])
                n_keep -= 1
                cached = n_keep * bs
                blocks = blocks[:n_keep]
                cow = False
                if cached <= 0:
                    cache.stats.misses += 1
                    return 0
        seq.blocks = list(blocks)
        seq.seen_tokens = cached
        seq.tokens = [int(t) for t in tokens[:cached]]
        seq.shared_blocks = n_keep
        if cow:
            self.kv_cache.copy_block(seq.blocks[-1], fresh)
            self.allocator.free([seq.blocks[-1]])     # drop our shared ref
            seq.blocks[-1] = fresh
            seq.shared_blocks = n_keep - 1
            # the tree already caches this content under the old block —
            # re-registering the fork would diverge, so stop here
            seq.register_stopped = True
            cache.stats.cow_forks += 1
        cache.stats.hits += 1
        cache.stats.hit_tokens += cached
        return cached

    def register_prefix(self, seq: DSSequenceDescriptor) -> None:
        """Register ``seq``'s newly completed full blocks into the radix
        tree (called wherever ``seen_tokens`` advances).  No-op unless the
        host knows the token values for every cached position."""
        cache = self.prefix_cache
        if cache is None or seq.register_stopped:
            return
        n_full = min(seq.seen_tokens // self.block_size, len(seq.blocks))
        if n_full <= seq.shared_blocks:
            return
        if len(seq.tokens) != seq.seen_tokens:
            seq.register_stopped = True   # values lost to the device
            return
        n, diverged = cache.insert(seq.tokens, seq.blocks,
                                   start_block=seq.shared_blocks)
        seq.shared_blocks += n
        if diverged:
            seq.register_stopped = True

    # ------------------------------------------------------------------ #
    # Host cold tier (kv_cache.host_tier): spool on LRU evict, restore
    # on attach.  free_blocks stays truthful — tier entries are NOT HBM
    # capacity; a restore consumes real free blocks through _allocate.
    # ------------------------------------------------------------------ #
    def _spool_nodes(self, nodes) -> None:
        """Prefix-cache eviction hook: demote the whole victim batch to
        host RAM — ONE ``gather_blocks`` dispatch + ONE sync for every
        victim block (the per-block dispatch cost at ~3-5 ms each made
        multi-block evictions pay serially), then split the host
        payload per block, each keyed by the token prefix it completes.
        Runs on the allocation path under KV pressure — never on a
        pressure-free steady-state decode tick."""
        import jax

        cache = self.prefix_cache
        tier = self.host_tier
        bs = self.kv_cache.block_rows       # a block's rows in a payload
        # keys read the parent chains BEFORE anything else — evict()
        # guarantees they are intact at hook time
        keys = [cache.node_tokens(n) for n in nodes]
        t0 = time.perf_counter()
        payload = self.kv_cache.gather_blocks([n.block for n in nodes])
        # gather_blocks device_gets, so the payload is host-resident
        # here; the explicit no-op block marks the bracket's sync point
        jax.block_until_ready(payload)
        tier.stats.spool_s.append(time.perf_counter() - t0)
        tier.stats.spool_blocks_per_call.append(len(nodes))
        import numpy as np

        for i, key in enumerate(keys):
            # row order follows the block list, so victim i's rows are
            # exactly [i*bs, (i+1)*bs).  COPY the slice (a bare or
            # ascontiguousarray'd slice is a VIEW — it would pin the
            # whole N-block gather buffer, so the tier's byte budget
            # could drop entries without releasing any memory)
            part = jax.tree_util.tree_map(
                lambda a, i=i: np.array(a[i * bs:(i + 1) * bs]), payload)
            tier.put(key, part)

    def _restore_blocks(self, tokens: Sequence[int], depth: int,
                        usable: int) -> List[int]:
        """Pull spooled continuation blocks of ``tokens`` (tree depth
        ``depth`` onward) back into HBM while they cover usable prompt
        positions.  The whole contiguous run of tier hits restores in
        ONE ``scatter_blocks`` dispatch + ONE sync: hits are popped
        first, their device blocks allocated in one :meth:`_allocate`
        call (which may itself evict-and-spool colder blocks — also
        batched now), the payloads concatenated and scattered together,
        then each block re-enters the radix tree holding the fresh
        refcount-1 reference as the tree's own with the attaching
        sequence's reference acquired on top.  Nothing allocates
        between the scatter and those acquires, so no eviction can
        recycle a block this very match is about to use (the caller
        has already acquired the in-HBM prefix for the same reason).
        Hits HBM cannot admit go straight back to the tier (never
        recounted as spools)."""
        import jax
        import numpy as np

        tier = self.host_tier
        cache = self.prefix_cache
        bs = self.block_size
        # pop the whole contiguous run of tier hits
        keys: List[tuple] = []
        payloads: List[dict] = []
        i = depth
        while i * bs < usable:
            key = tuple(int(t) for t in tokens[:(i + 1) * bs])
            payload = tier.get(key)
            if payload is None:
                break
            keys.append(key)
            payloads.append(payload)
            i += 1
        if not keys:
            return []
        # allocate for as many hits as HBM admits (deepest-first
        # surrender keeps the restored span a contiguous prefix)
        blks: List[int] = []
        while keys:
            try:
                blks = self._allocate(len(keys))
                break
            except RuntimeError:
                tier.put(keys.pop(), payloads.pop(), count_spool=False)
        if not blks:
            return []
        merged = (payloads[0] if len(payloads) == 1 else
                  jax.tree_util.tree_map(
                      lambda *parts: np.concatenate(parts, axis=0),
                      *payloads))
        t0 = time.perf_counter()
        self.kv_cache.scatter_blocks(blks, merged)
        # the scatter is async-dispatched; block so the restore
        # latency stat measures the transfer, not the dispatch
        jax.block_until_ready(self.kv_cache.cache)
        tier.stats.restore_s.append(time.perf_counter() - t0)
        tier.stats.restore_blocks_per_call.append(len(blks))
        tier.stats.restored_blocks += len(blks)
        for key, blk in zip(keys, blks):
            cache.insert_restored(key, blk)
            self.allocator.acquire([blk])
        return blks

    def record_fed_tokens(self, seq: DSSequenceDescriptor, tokens) -> None:
        """Append host-known token values the engine just wrote KV for
        (keeps ``seq.tokens`` in lockstep with ``seen_tokens``)."""
        if self.prefix_cache is None or seq.register_stopped:
            return
        seq.tokens.extend(int(t) for t in tokens)
