"""FastGen continuous-batching engine (reference: inference/v2/engine_v2.py
``InferenceEngineV2`` — ``put:107`` / ``query:153`` / ``can_schedule:181`` /
``flush:210``).

TPU-native shape discipline: the ragged forward is ONE jitted program over
static shapes ``(token_budget T, max_seqs S, max_blocks B)`` — exactly the
property Dynamic SplitFuse gives the reference (fixed token budget per
forward), which on TPU also means exactly one compilation.  Scheduling is
host-side python (as in the reference); device work is the single jitted
ragged step.

``put`` runs one forward over whatever chunks fit the budget and returns the
next-token logits per *fully scheduled* sequence; prompts longer than the
remaining budget are chunked (SplitFuse) and continue on the next ``put``
round via the sequence's ``pending`` queue.  Every step program also takes
the argmax of its logits rows, so a caller that samples greedily asks for
the tokens (``put(..., greedy=True)``) and one int32 a row crosses to the
host instead of a vocabulary row.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.inference.v2.modules.attention import (
    kv_spec,
    shard_ragged_params,
)
from deepspeed_tpu.inference.v2.ragged import (CacheLayoutError,
                                               DSStateManager,
                                               RaggedBatchWrapper)
from deepspeed_tpu.observability.tracer import (SpanHandle,
                                                build_telemetry_from_here,
                                                open_span, setup_span)
from deepspeed_tpu.utils.compile_cache import key_cache_on_names
from deepspeed_tpu.utils.logging import log_dist


def _device_decode_batch(tables, pos, tok, block_size: int,
                         max_blocks: int, state_slot=None, tables_win=None):
    """Ragged batch dict for a one-token-per-slot decode round, with the
    KV write target derived ON DEVICE from the block tables — the single
    source of the per-step decode metadata contract (shared by the
    scanned ``decode_loop`` body and the per-call ``decode_step``).
    ``state_slot`` ([S], a model with recurrent state): each row's slot of
    the state pool; every row is its own chunk of one token.
    ``tables_win`` ([S, B], a model with ``kv_groups``): the window
    group's tables, and the write target in its pools beside them."""
    S = tables.shape[0]
    if state_slot is not None:
        return {**_device_decode_batch(tables, pos, tok, block_size,
                                       max_blocks, tables_win=tables_win),
                "state_slot": state_slot,
                "chunk_start": jnp.arange(S, dtype=jnp.int32)}
    if tables_win is not None:
        win = _device_decode_batch(tables_win, pos, tok, block_size,
                                   max_blocks)
        return {**_device_decode_batch(tables, pos, tok, block_size,
                                       max_blocks),
                "block_tables_win": tables_win,
                "kv_dest_win": win["kv_dest"]}
    slot = jnp.arange(S, dtype=jnp.int32)
    blk = jnp.take_along_axis(
        tables, jnp.clip(pos // block_size, 0, max_blocks - 1)[:, None],
        axis=1)[:, 0]
    return {
        "token_ids": tok,
        "token_slot": slot,
        "token_pos": pos,
        "kv_dest": blk * block_size + pos % block_size,
        "block_tables": tables,
        "context_lens": pos + 1,
        "logits_idx": slot,
    }


def _device_verify_batch(tables, pos, tok, block_size: int,
                         max_blocks: int, k_tokens: int):
    """Ragged batch dict for a speculative VERIFY round: ``k_tokens``
    consecutive-position tokens per slot (the fed token plus the drafted
    lookahead), rows slot-major, with ``logits_idx`` selecting EVERY row
    so the forward returns all K candidate logits per sequence."""
    S = tables.shape[0]
    slot = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k_tokens)
    p2 = pos[:, None] + jnp.arange(k_tokens, dtype=jnp.int32)[None, :]
    blk = jnp.take_along_axis(
        tables, jnp.clip(p2 // block_size, 0, max_blocks - 1), axis=1)
    p = p2.reshape(-1)
    return {
        "token_ids": tok.reshape(-1),
        "token_slot": slot,
        "token_pos": p,
        "kv_dest": blk.reshape(-1) * block_size + p % block_size,
        "block_tables": tables,
        "context_lens": pos + k_tokens,
        "logits_idx": jnp.arange(S * k_tokens, dtype=jnp.int32),
    }


def _pack_tables_positions(seqs, max_seqs: int, max_blocks: int):
    """Host-side [S, B] block table + [S] position arrays for live decode
    sequences (trash-padded), shared by ``decode_loop`` and
    ``decode_step``'s device-state upload."""
    from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
        BlockedAllocator)

    tables = np.full((max_seqs, max_blocks), BlockedAllocator.TRASH_BLOCK,
                     np.int32)
    pos = np.zeros((max_seqs,), np.int32)
    for i, seq in enumerate(seqs):
        tables[i, :len(seq.blocks)] = seq.blocks
        pos[i] = seq.seen_tokens
    return tables, pos


def _pack_window_tables(seqs, max_seqs: int, max_blocks: int):
    """Host-side [S, B] tables of the window group (``kv_groups``): each
    sequence's live entries, trash below and past them."""
    from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
        BlockedAllocator)

    tables = np.full((max_seqs, max_blocks), BlockedAllocator.TRASH_BLOCK,
                     np.int32)
    for i, seq in enumerate(seqs):
        seq.write_window_row(tables[i])
    return tables


@dataclasses.dataclass
class PreparedBatch:
    """One ragged batch built by :meth:`InferenceEngineV2.prepare` and not
    yet on the device.  Host memory only; :meth:`~InferenceEngineV2.launch`
    dispatches it, :meth:`~InferenceEngineV2.discard` drops it."""

    #: uids in slot order, and whether this batch drains each one's queue
    scheduled: List[int]
    drained: List[bool]
    #: tokens of each slot's chunk
    chunk_sizes: List[int]
    #: rows of the step program (with ``tile`` its ``_get_step`` key)
    bucket: int
    tile: Optional[int]
    #: the packed metadata (``pack_metadata``): what ``launch`` uploads
    packed: np.ndarray
    #: uid -> the entry of ``packed`` that waits for a late row's token
    late: Dict[int, int]
    #: what ``prepare`` queued and created, and the prefix cache's attach
    #: counters before it: what ``discard`` takes back
    queued: Dict[int, int] = dataclasses.field(default_factory=dict)
    created: List[int] = dataclasses.field(default_factory=list)
    attach_stats: Optional[Dict[str, int]] = None


def _named(fn, name: str):
    """``fn`` under the name its jitted program is to carry: the XLA
    module (``jit_decode_step``), the profiler's module line and the head
    of every ``op_name`` (``jit(decode_step)/layers_0/mlp/...``); all
    step programs were ``jit(run)``."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class InferenceEngineV2:
    """reference engine_v2.py:30."""

    @setup_span("setup/engine_init")
    def __init__(self, model: Any, params: Any,
                 config: Optional[RaggedInferenceEngineConfig] = None):
        self.config = config or RaggedInferenceEngineConfig()
        # the step programs' scopes are read by name from a profile
        key_cache_on_names()
        #: the scheduler's Tracer (``attach_tracer``): the engine's spans
        #: nest under whatever span of it is open (the tick's phase)
        self.tracer = None
        #: number of the last step program dispatched (every ``decode_step``
        #: / ``ragged_step_*`` / ``verify_step_*`` launch, engine-wide,
        #: from 1): what its dispatch span and the wait that retires it
        #: are named by
        self.last_launch = 0
        #: the ``observability/program*`` counters and this engine's
        #: ``time_to_first_launch_s`` (``occupancy``)
        self._build_telemetry = build_telemetry_from_here()
        sm_cfg = self.config.state_manager
        kv_cfg = self.config.kv_cache
        max_pos = getattr(model, "max_positions", None)
        if max_pos is not None and sm_cfg.max_context > max_pos:
            raise ValueError(
                f"state_manager.max_context={sm_cfg.max_context} exceeds "
                f"the model's learned position table ({max_pos}); "
                f"positions past it would silently alias the last row")
        self.model = model
        self.params = params
        # a model states what its cache keeps beside plain k / v pools:
        # recurrent state in slots (``state_spec``), a pool row of its own
        # (``kv_row``), two groups of KV layers behind two block tables
        # (``kv_groups``), a cache per pass of a stack of layers that runs
        # several times a token (``kv_passes``).  The state manager builds
        # that layout and knows
        # what it cannot serve (``require``); each extension's operands are
        # handed to the step programs below.
        state_spec = getattr(model, "state_spec", None)
        kv_groups = getattr(model, "kv_groups", None)
        self._stateful = state_spec is not None
        self._grouped = kv_groups is not None
        #: state slots AND layers with keys and values: every dispatch span
        #: closes with what the launch asks of both (``hyb_*``)
        self._hybrid = self._stateful and \
            len(state_spec["layers"]) < model.num_layers
        #: caches a layer keeps (``kv_passes``): on every dispatch span
        self._passes = int(getattr(model, "kv_passes", 1))
        #: a dispatch span counts what its launch's rows ask for
        self._counts_rows = self._passes > 1 or self._hybrid
        #: cached positions a query row of the model reads (its learned
        #: sparse-attention indexer's top-k); None: every one
        self.index_topk: Optional[int] = getattr(model, "index_topk", None)
        #: counters the model decides on the device (how its router's slots
        #: fell: ``RaggedLongcatFlash``), by name: its forward returns them
        #: as one int32 vector beside the logits, and every greedy step
        #: program of such a model returns ``next_tokens`` with that
        #: vector BEHIND its ``max_seqs`` tokens, so they cross to the host
        #: in the transfer that brings the tokens (``counters_of``).  ():
        #: ``next_tokens`` is int32[max_seqs], every program as it was
        self.step_counters = tuple(getattr(model, "step_counters", ()))
        try:
            self.state_manager = DSStateManager(
                sm_cfg, kv_cfg, num_layers=model.num_layers,
                num_kv_heads=model.num_kv_heads, head_dim=model.head_dim,
                dtype=getattr(model.config, "dtype", None),
                state_spec=state_spec, kv_row=getattr(model, "kv_row", None),
                **({"kv_groups": kv_groups} if self._grouped else {}),
                **({"kv_passes": self._passes} if self._passes > 1 else {}))
        except CacheLayoutError as e:   # of which model, for the message
            raise CacheLayoutError(f"{type(model).__name__}: {e}") from None
        if self.state_manager.kv_cache.quantized:
            if not getattr(model, "supports_quantized_kv", False):
                raise ValueError(
                    f"kv_cache.dtype=int8 needs a model whose attention "
                    f"path quantizes on insert and fuses the dequant "
                    f"(RaggedLlama family); {type(model).__name__} would "
                    f"silently write float KV into an int8 pool")
            if getattr(model, "tp", 1) > 1:
                raise ValueError(
                    "int8 KV does not compose with tensor parallelism "
                    "yet — the scale records need their own kv-head "
                    "partition spec")
            if model.head_dim % 128 != 0:
                log_dist(
                    f"kv_cache.dtype=int8 with head_dim="
                    f"{model.head_dim}: the fused-dequant Pallas "
                    f"kernels need 128-aligned head dims, so attention "
                    f"reads take the XLA gather+dequant path — the "
                    f"capacity win (int8 bytes in HBM) stands, the "
                    f"decode-bandwidth win does not",
                    level=logging.WARNING)
        self._max_blocks = -(-sm_cfg.max_context // kv_cfg.block_size)
        self._batch = RaggedBatchWrapper(
            token_budget=sm_cfg.max_ragged_batch_size,
            max_seqs=sm_cfg.max_ragged_sequence_count,
            max_blocks=self._max_blocks,
            block_size=kv_cfg.block_size,
            state_scratch=(self.state_manager.state_pool.scratch
                           if self._stateful else None),
            window=self.state_manager.window)
        # Tensor parallelism (reference inference/v2/model_implementations/
        # sharding/): the model is mesh-bound -> place params by the
        # Megatron split rules and the KV pool kv-head-split, so the
        # shard_map'd step reads them without any resharding
        if getattr(model, "tp", 1) > 1:
            from jax.sharding import NamedSharding

            self.params = shard_ragged_params(params, model.mesh)
            self.state_manager.kv_cache.cache = jax.tree.map(
                lambda x: jax.device_put(
                    x, NamedSharding(model.mesh, kv_spec(x))),
                self.state_manager.kv_cache.cache)
        # Token-dim buckets of an engine that packs chunks back to back
        # (its budget is no whole number of PREFILL_TILEs; a tiled engine
        # sizes its programs in ``_build_batch``): a decode step (a handful
        # of tokens) compiles to a SMALL program instead of the
        # prefill-sized one — the paged kernel's grid is proportional to
        # the token capacity, so running every decode at the full SplitFuse
        # budget costs a prefill's grid per generated token. Powers-of-4
        # keeps compile count low.
        budget = sm_cfg.max_ragged_batch_size
        self._buckets = sorted({b for b in (16, 64, 256, 1024)
                                if b < budget} | {budget})
        # donate the KV pool: the old cache is dead the moment
        # state_manager.kv_cache.update() stores the new one, and donation
        # lets XLA update the pool in place instead of copying it per step
        self._steps: Dict[int, Any] = {}
        #: device-resident decode metadata (block tables + positions),
        #: re-uploaded only when the host scheduler changes a table
        self._dev_decode_state: Optional[Dict[str, Any]] = None
        #: the jitted gather behind ``decode_step(rows=...)``
        #: (``_build_token_gather``)
        self._gather_tokens = None
        #: ``sm.win_released`` as of the last span that reported it
        self._win_reported = 0
        log_dist(
            f"InferenceEngineV2: token_budget={sm_cfg.max_ragged_batch_size} "
            f"max_seqs={sm_cfg.max_ragged_sequence_count} "
            f"kv_blocks={self.state_manager.allocator.num_blocks} "
            f"block_size={kv_cfg.block_size}" + (
                f" passes={self._passes} cache_layers={self._cache_layers}"
                if self._passes > 1 else "") + (
                " state_slots={0.num_slots} state_bytes_per_seq="
                "{0.per_sequence_bytes} state_bytes={0.total_bytes}".format(
                    self.state_manager.state_pool)
                if self._stateful else ""), ranks=[0])

    def attach_tracer(self, tracer) -> None:
        """Record this engine's spans on ``tracer`` (None detaches)."""
        self.tracer = tracer

    def _launched(self, span, step, rows=()) -> int:
        """Count the step program just dispatched; a live dispatch span
        closes with the launch record: that number and the jitted
        program's name (the ``jit(<program>)`` that heads the ``op_name``
        of its device operations).  A looped stack (``kv_passes``) adds
        its trips, its caches and what ``rows`` ([(positions cached, tokens
        fed)] a sequence) ask of each of them (``loop_seqs``,
        ``loop_tokens``, the context tokens read ``loop_ctx_tokens`` and the
        causal (query, key) pairs ``loop_attn_pairs``; names no other
        span's counter has: readers sum a counter over a tick's spans).
        A model with state slots beside KV layers adds what the launch
        asks of both: ``hyb_seqs`` (its live one-token rows),
        ``hyb_tokens`` (tokens fed), ``hyb_ctx_tokens`` (cached tokens
        those one-token rows read, their own included), ``hyb_attn_pairs``
        (causal (query, key) pairs of its chunk rows) and
        ``hyb_state_seqs`` (sequences whose state it reads and writes)."""
        self.last_launch += 1
        if type(span) is SpanHandle:
            span.attrs = {"launch": self.last_launch,
                          "program": step.__name__}
            if self._hybrid:
                span.attrs.update(
                    hyb_seqs=sum(n == 1 for _, n in rows),
                    hyb_tokens=sum(n for _, n in rows),
                    hyb_ctx_tokens=sum(a + 1 for a, n in rows if n == 1),
                    hyb_attn_pairs=sum(n * (2 * a + n + 1) // 2
                                       for a, n in rows if n > 1),
                    hyb_state_seqs=len(rows))
            if self._passes > 1:
                span.attrs.update(
                    passes=self._passes, cache_layers=self._cache_layers,
                    loop_seqs=len(rows),
                    loop_tokens=sum(n for _, n in rows),
                    loop_ctx_tokens=sum(a + n for a, n in rows),
                    loop_attn_pairs=sum(n * (2 * a + n + 1) // 2
                                        for a, n in rows))
        return self.last_launch

    @property
    def _cache_layers(self) -> int:
        """K/V caches the step programs read and write: one a (KV layer,
        pass)."""
        kv = self.state_manager.kv_cache
        return kv.passes * len(kv.kv_layers)

    # ------------------------------------------------------------------ #
    # Scheduling predicates (reference can_schedule:181 / query:153)
    # ------------------------------------------------------------------ #
    def query(self, uid: int) -> Dict[str, int]:
        """Per-sequence status (reference ``query`` returns max lengths)."""
        seq = self.state_manager.get_sequence(uid)
        sm = self.state_manager
        committed = (seq.seen_tokens + len(seq.pending)) if seq else 0
        slack = (len(seq.blocks) * sm.block_size - committed) if seq else 0
        headroom = min(sm.free_blocks * sm.block_size + max(slack, 0),
                       self.config.state_manager.max_context - committed)
        return {
            "tracked": seq is not None,
            "seen_tokens": seq.seen_tokens if seq else 0,
            "pending_tokens": len(seq.pending) if seq else 0,
            "free_blocks": sm.free_blocks,
            "max_new_tokens": max(headroom, 0),
        }

    def can_schedule(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        """Would scheduling `lengths[i]` new tokens for `uids[i]` fit ONE
        ``put`` forward right now: the token budget, the sequence slots,
        the rows of the two-segment layout (a chunk longer than one token
        takes whole tiles, see ``_build_batch``) and the free KV blocks?"""
        if len(uids) > self._batch.max_seqs:
            return False
        if sum(lengths) > self._batch.token_budget:
            return False
        tile = self._prefill_tile()
        if tile and sum(-(-n // tile) * tile for n in lengths
                        if n > 1) > self._batch.token_budget:
            return False
        return self.can_allocate(uids, lengths)

    def can_allocate(self, uids: Sequence[int],
                     lengths: Sequence[int]) -> bool:
        """Do `lengths[i]` more tokens for `uids[i]` fit the free KV blocks
        and ``max_context``?  The part of :meth:`can_schedule` that holds
        for any step program (``verify_step`` feeds K tokens a sequence
        without going through ``put``'s row layout)."""
        max_context = self.config.state_manager.max_context
        blocks = 0
        for uid, n in zip(uids, lengths):
            seq = self.state_manager.get_sequence(uid)
            have = (seq.seen_tokens + len(seq.pending)) if seq else 0
            if have + n > max_context:
                return False
            if seq is None:
                blocks += -(-n // self.state_manager.block_size)
            else:
                blocks += self.state_manager.blocks_needed(seq, n)
        if self._stateful and sum(
                self.state_manager.get_sequence(u) is None
                for u in uids) > self.state_manager.state_pool.free:
            return False            # a new sequence needs a state slot
        sm = self.state_manager
        if self._grouped:
            # (more sequences tracked than one forward has rows; what any
            # of them holds below its band counts for nothing)
            sm.release_windows()
            if sum(sm.window_blocks_needed(sm.get_sequence(u), n)
                   for u, n in zip(uids, lengths)) \
                    > sm.win_allocator.free_blocks:
                return False        # the window group's pool binds
        return blocks <= self.state_manager.free_blocks

    def attach_prefix(self, uid: int, tokens: Sequence[int]) -> int:
        """Create sequence ``uid`` (it must not be live) attached to the
        warm KV blocks covering the longest cached prefix of ``tokens``.
        Returns the number of prefill tokens skipped (0 when the prefix
        cache is disabled or misses) — the caller feeds only
        ``tokens[cached:]`` through :meth:`put`.  The serving scheduler
        calls this at admission so SplitFuse chunking starts past the
        cached span."""
        seq = self.state_manager.get_or_create_sequence(uid)
        return self.state_manager.attach_prefix(
            seq, [int(t) for t in tokens])

    @property
    def prefix_cache_stats(self):
        """Live :class:`PrefixCacheStats` (None when caching is off)."""
        pc = self.state_manager.prefix_cache
        return pc.stats if pc is not None else None

    # ------------------------------------------------------------------ #
    # put (reference engine_v2.py:107)
    # ------------------------------------------------------------------ #
    def put(self, uids: Sequence[int],
            tokens: Sequence[Sequence[int]],
            sync: bool = True, greedy: bool = False) -> Dict[int, Any]:
        """Schedule new tokens for the given sequences and run forwards until
        every scheduled chunk has been consumed.

        Returns ``{uid: logits[vocab]}`` for the sequences whose LAST token
        was processed this call (i.e. every uid — chunked prompts loop
        internally until drained, as the reference's MII loop does across
        ``put`` calls).  With ``greedy=True`` returns ``{uid: token}``
        instead: the argmax the step program itself took of those rows
        (the first index of the maximum, as ``np.argmax`` of the fetched
        row gives), so a forward costs ONE transfer of ``int32[max_seqs]``
        and the logits never leave the device.  It is the same program
        either way (as :meth:`decode_step` returns both): which of its
        outputs is fetched is all that ``greedy`` chooses.  With
        ``sync=False`` the values are device arrays (no blocking download)
        so a caller can pipeline further device work — e.g. sampling —
        before the first host sync; see also :meth:`decode_step` for the
        fully device-resident decode round.

        Each forward is :meth:`prepare` and :meth:`launch` back to back; a
        caller that has other work for the host between the two (the
        serving scheduler, while the program before is still running)
        calls them itself.
        """
        self._enqueue(uids, tokens)
        results: Dict[int, Any] = {}
        while self._has_pending(uids):
            results.update(self._run_one_batch(uids, sync=sync,
                                               greedy=greedy))
        return results

    def _enqueue(self, uids, tokens) -> "tuple[Dict[int, int], List[int]]":
        """Put ``tokens[i]`` on the pending queue of ``uids[i]`` (a new
        sequence first skips the prefill of any cached prefix).  Returns
        ``({uid: tokens queued}, [uids created here])``: what
        :meth:`discard` takes back."""
        max_context = self.config.state_manager.max_context
        queued: Dict[int, int] = {}
        created: List[int] = []
        for uid, toks in zip(uids, tokens):
            if len(toks) == 0:
                raise ValueError(f"put: empty token list for uid {uid}")
            fresh = self.state_manager.get_sequence(uid) is None
            seq = self.state_manager.get_or_create_sequence(uid)
            if fresh:
                created.append(uid)
                # new sequence: skip the prefill of any cached prefix
                # (sequences pre-created via attach_prefix already did)
                cached = self.state_manager.attach_prefix(seq, toks)
                if cached:
                    toks = toks[cached:]
            if seq.seen_tokens + len(seq.pending) + len(toks) > max_context:
                raise RuntimeError(
                    f"sequence {uid} would exceed max_context {max_context} "
                    f"({seq.seen_tokens} seen + {len(seq.pending)} pending "
                    f"+ {len(toks)} new); check can_schedule()/query() first")
            seq.pending.extend(int(t) for t in toks)
            queued[uid] = queued.get(uid, 0) + len(toks)
        return queued, created

    def _get_step(self, bucket: int, prefill_tile: Optional[int] = None):
        """One jitted (model fwd ∘ metadata unpack ∘ argmax) program per
        (rows of the token buffer, tile mode), returning ``(logits
        [max_seqs, vocab], next_tokens int32[max_seqs], new cache)``; the
        KV pool is donated.  With ``prefill_tile`` the rows are the
        two-segment layout of ``_build_batch``: ``max_seqs`` single-token
        rows, then whole tiles."""
        key = (bucket, prefill_tile)
        step = self._steps.get(key)
        if step is None:
            from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
                unpack_metadata)

            S, B = self._batch.max_seqs, self._max_blocks
            fields = {"state": self._stateful, "win": self._grouped}

            def run(params, cache, packed):
                batch = unpack_metadata(packed, bucket, S, B, **fields)
                logits, new_cache, *counts = self.model(
                    params, cache, batch, prefill_tile=prefill_tile)
                with jax.named_scope("sample_argmax"):
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    if counts:          # (``step_counters``)
                        nxt = jnp.concatenate([nxt, counts[0]])
                return logits, nxt, new_cache

            step = jax.jit(_named(run, f"ragged_step_T{bucket}" + (
                "_tiled" if prefill_tile else "")), donate_argnums=(1,))
            self._steps[key] = step
        return step

    def _has_pending(self, uids) -> bool:
        return any(self.state_manager.get_sequence(u) is not None
                   and self.state_manager.get_sequence(u).pending
                   for u in uids)

    #: q-tile of the tiled prefill kernel (the reference atom_builder's
    #: work-unit height).  An engine whose token budget is a whole number
    #: of tiles packs EVERY ragged batch in the two-segment layout: the
    #: chunks of one token in ``max_seqs`` rows at the front, every longer
    #: chunk tile-aligned behind them.  Which segment a chunk goes to is
    #: read from its length, so a tick that mixes decodes with prefill
    #: chunks still runs its prefill through the tiled kernel.
    PREFILL_TILE = 128

    def _prefill_tile(self) -> Optional[int]:
        """The tile of this engine's ragged batches, or None when the
        token budget does not divide into tiles (such an engine packs
        chunks back to back and attends through the token-grid kernel)."""
        tile, budget = self.PREFILL_TILE, self._batch.token_budget
        return tile if budget >= tile and budget % tile == 0 else None

    def prepare(self, uids: Sequence[int],
                tokens: Optional[Sequence[Sequence[int]]] = None,
                late: Sequence[int] = ()) -> Optional["PreparedBatch"]:
        """Build ONE ragged batch under the token budget and stop short of
        the device: queue ``tokens[i]`` for ``uids[i]`` (as :meth:`put`
        does; None: what is pending already), SplitFuse chunking, KV
        allocation, window release, rows, bucket, positions, block tables,
        state slots, the packed metadata as a numpy vector.  Nothing is
        uploaded or dispatched and no sequence advances: ``seen_tokens``
        moves at :meth:`launch`, and :meth:`discard` takes the queued
        tokens back.

        ``late`` names the one-token rows whose token does not exist yet
        (the argmax of a program still running): their chunk is a
        placeholder, and :meth:`launch` is handed the values.  Everything
        else a row needs (its position, its KV slot, its block table) is
        host state the moment the program before was dispatched, so a batch
        can be prepared while that program runs.

        Returns the :class:`PreparedBatch`, or None when nothing is
        pending."""
        late = set(late)
        stats = self.prefix_cache_stats
        snap = stats.attach_snapshot() \
            if tokens is not None and stats is not None else None
        queued, created = self._enqueue(uids, tokens) \
            if tokens is not None else ({}, [])
        with open_span(self.tracer, "engine/build_batch") as span:
            prepared = self._build_batch(uids, late)
            if prepared is None:
                return None
            prepared.queued, prepared.created = queued, created
            prepared.attach_stats = snap
            if type(span) is SpanHandle:
                span.attrs = self._batch_counters(prepared.bucket)
        return prepared

    def _build_batch(self, uids, late=()) -> Optional["PreparedBatch"]:
        """The batch of :meth:`prepare` from what is pending."""
        sm = self.state_manager
        self._batch.clear()
        tile = self._prefill_tile()
        if tile:
            self._batch.set_alignment(tile)
        elif self._stateful:
            raise CacheLayoutError(
                f"a model with recurrent state runs its prompt chunks "
                f"through whole tiles: max_ragged_batch_size "
                f"{self._batch.token_budget} is no multiple of "
                f"{self.PREFILL_TILE}")
        scheduled: List[int] = []
        drained: List[bool] = []
        if self._grouped:           # what fell out of the bands, first
            sm.release_windows()
        for uid in uids:
            seq = sm.get_sequence(uid)
            if seq is None or not seq.pending:
                continue
            n = self._batch.fit(len(seq.pending))    # Dynamic SplitFuse
            if n == 0:
                continue     # a later one-token chunk may still have a row
            chunk = seq.pending[:n]
            sm.maybe_allocate_kv(seq, n)
            self._batch.insert_sequence(seq, np.asarray(chunk, np.int32))
            scheduled.append(uid)
            drained.append(n == len(seq.pending))
        if not scheduled:
            return None

        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
            pack_metadata)

        if tile:
            # tiled segment: tile x 2^k rows up to the budget, or none
            need = self._batch.tiled_rows
            tiled = tile if need else 0
            while tiled < need:
                tiled *= 2
            bucket = self._batch.max_seqs + min(
                tiled, self._batch.token_budget)
        else:
            bucket = min(b for b in self._buckets
                         if b >= self._batch.current_tokens)
        packed = pack_metadata(self._batch.finalize(bucket))
        sizes, starts = self._batch.chunk_sizes, self._batch.starts
        rows = {}
        for slot, uid in enumerate(scheduled):
            if uid in late:
                if sizes[slot] != 1:
                    raise ValueError(
                        f"prepare: late row {uid} is a chunk of "
                        f"{sizes[slot]} tokens; only a one-token row can "
                        f"wait for the program before it")
                rows[uid] = starts[slot]    # token_ids heads the vector
        return PreparedBatch(scheduled, drained, sizes, bucket, tile,
                             packed, rows)

    def _state_counters(self) -> Dict[str, int]:
        """What a dispatch span says of the slot pool: slots held, and the
        bytes they and the whole pool (scratch slot included) take."""
        pool = self.state_manager.state_pool
        return {"state_slots": pool.held, "state_bytes": pool.held_bytes,
                "state_bytes_total": pool.total_bytes}

    def _batch_counters(self, bucket: int) -> Dict[str, int]:
        """What the ``engine/build_batch`` span closes with: the useful
        tokens of the rows they are padded to, and what the batch just
        built asks of each family's kernels."""
        sm = self.state_manager
        rows = list(zip(self._batch.sequences, self._batch.chunk_sizes))
        attrs = {"tokens": self._batch.current_tokens, "bucket": bucket}
        # a model that states its own pool row (a latent one: no k / v)
        latent = bool(sm.kv_cache.kv_row)
        # sequences with a chunk in the tile segment here, and those
        # chunks' (start, tokens)
        tiled = [(s.seen_tokens, n) for s, n in rows if n > 1]
        if self._stateful or latent:
            attrs.update(chunk_seqs=len(tiled),
                         chunk_tokens=sum(n for _, n in tiled))
        if self._stateful:
            attrs.update(self._state_counters())
        if self._grouped:
            attrs.update(self._window_counters(
                [s for s, n in rows if n == 1], tiled))
        tile = self._prefill_tile()
        if self.index_topk is not None:
            attrs.update(self.index_counters(
                [s.seen_tokens for s, n in rows if n == 1], tiled))
            if tile and tiled:
                attrs.update(self._sparse_step_counters(
                    tiled, (bucket - self._batch.max_seqs) // tile, tile))
        elif tile and tiled and not sm.kv_cache.quantized:
            count = self._latent_step_counters if latent \
                else self._chunk_step_counters
            attrs.update(count(
                tiled, (bucket - self._batch.max_seqs) // tile, tile))
        # what the one-token read (the decode walk, the absorbed read) must
        # do: the table blocks the batch's one-token rows hold up to the
        # position they feed
        bs = sm.block_size
        attrs["row_blocks"] = sum(
            s.seen_tokens // bs + 1 for s, n in rows if n == 1)
        if latent:
            # what the expanded read must do: the causal (query, key)
            # pairs of the chunks, and the context rows to expand (each
            # chunk's end position)
            attrs.update(
                attn_pairs=sum(n * (2 * a + n + 1) // 2 for a, n in tiled),
                ctx_rows=sum(a + n for a, n in tiled))
        return attrs

    def launch(self, prepared: "PreparedBatch",
               late_tokens: Optional[Dict[int, int]] = None):
        """Hand a prepared batch to the device: the late rows' tokens
        into the token buffer, the ONE upload, the dispatch, then what
        follows a dispatch for every row (``seen_tokens``, the token
        record, the prefix cache).  Returns ``(logits [max_seqs, vocab],
        next_tokens int32[max_seqs], launch number)``, the first two on
        the device and NOT fetched: slot ``i`` is
        ``prepared.scheduled[i]``, and only a slot whose
        ``prepared.drained[i]`` is set holds its sequence's last row."""
        sm = self.state_manager
        late_tokens = late_tokens or {}
        if late_tokens.keys() != prepared.late.keys():
            raise ValueError(
                f"launch: late rows {sorted(prepared.late)} were prepared, "
                f"tokens came for {sorted(late_tokens)}")
        with open_span(self.tracer, "engine/upload"):
            if late_tokens:
                prepared.packed[[prepared.late[uid] for uid in late_tokens]] \
                    = list(late_tokens.values())
            packed = jnp.asarray(prepared.packed)       # ONE upload
        # host↔device alignment: a jax.profiler capture shows this named
        # bracket on the host track lined up with the XLA execution it
        # dispatched
        with open_span(self.tracer, "engine/ragged_step") as span:
            step = self._get_step(prepared.bucket, prepared.tile)
            logits, nxt, new_cache = step(self.params, sm.kv_cache.cache,
                                          packed)
            launch = self._launched(span, step, [
                (sm.get_sequence(uid).seen_tokens, n) for uid, n in zip(
                    prepared.scheduled, prepared.chunk_sizes)]
                if self._counts_rows else ())
        sm.kv_cache.update(new_cache)
        for uid, n in zip(prepared.scheduled, prepared.chunk_sizes):
            seq = sm.get_sequence(uid)
            if uid in late_tokens:          # in place of the placeholder
                seq.pending[0] = int(late_tokens[uid])
            sm.record_fed_tokens(seq, seq.pending[:n])
            seq.seen_tokens += n
            del seq.pending[:n]
            sm.register_prefix(seq)
        return logits, nxt, launch

    def discard(self, prepared: "PreparedBatch") -> None:
        """Drop a batch that was prepared and will not be launched: the
        tokens :meth:`prepare` queued leave the pending queues (the late
        rows' placeholders among them) and a sequence it created is
        flushed, so engine, allocator and prefix-cache statistics stand as
        if the batch had never been built, but for the KV blocks a
        sequence that was there before took for its chunk: those stay with
        it (its next chunk uses them, ``flush`` frees them)."""
        sm = self.state_manager
        for uid, n in prepared.queued.items():
            seq = sm.get_sequence(uid)
            if seq is not None and n:
                del seq.pending[len(seq.pending) - n:]
        if prepared.created:
            self.flush(prepared.created)
        if prepared.attach_stats is not None:
            self.prefix_cache_stats.restore_attach(prepared.attach_stats)

    def _run_one_batch(self, uids, sync: bool = True,
                       greedy: bool = False) -> Dict[int, Any]:
        """One forward of :meth:`put`: prepare, launch, and return the
        logits row (its argmax with ``greedy``) of every slot whose
        pending queue drained.  With ``sync`` the host waits once, for the
        one output asked for: the span of that wait is
        ``engine/fetch_logits`` for the logits and ``fetch`` for the token
        vector, and closes with the step's ``launch``."""
        prepared = self.prepare(uids)
        if prepared is None:
            return {}
        logits, nxt, launch = self.launch(prepared)
        rows = nxt if greedy else logits
        done = [(slot, uid) for slot, (uid, last) in enumerate(
            zip(prepared.scheduled, prepared.drained)) if last]
        if not sync:
            return {uid: rows[slot] for slot, uid in done}  # lazy rows
        if not done:
            return {}
        with open_span(self.tracer, "fetch" if greedy else
                       "engine/fetch_logits") as span:
            host = jax.device_get(rows)
            host = host.tolist() if greedy else np.asarray(host, np.float32)
            if type(span) is SpanHandle:
                span.attrs = {"launch": launch,
                              **(self.counters_of(host) if greedy else {})}
        return {uid: host[slot] for slot, uid in done}

    # ------------------------------------------------------------------ #
    # Pipelined per-step decode (the put() scheduling path without the
    # per-token host sync): the host still runs FastGen scheduling every
    # step — KV allocation, block tables, position metadata — but token
    # feedback stays on device.  ``decode_step`` accepts the PREVIOUS
    # step's (device) logits argmax as a device array and returns device
    # logits, so a serving loop chains N steps with exactly one
    # ``block_until_ready`` at the end.  A blocking download stalls the
    # host until the device drains, while async dispatches pipeline —
    # the asymmetry the reference's pinned ★fast_host_buffer.cu staging
    # exists to hide.
    # ------------------------------------------------------------------ #
    def decode_step(self, uids: Sequence[int], tokens,
                    greedy: bool = False,
                    rows: Optional[Sequence[int]] = None):
        """One continuous-batching decode step with device-resident token
        feedback.

        ``tokens`` is each sequence's next input token: a host list of ints
        OR a ``jax.Array`` of shape ``[len(uids)]`` (int32) — typically
        the greedy tokens the previous call returned, which never leave
        the device.  The caller keeps that array: the serving scheduler
        holds the ``next_tokens`` of the step in flight, feeds it to the
        next step before it has fetched it, and reports the values through
        :meth:`record_device_tokens` once it has, which is what the prefix
        cache registers decoded blocks from.  Every ``uids[i]`` must be
        live with no pending prompt tokens (run :meth:`put` first).

        ``rows`` (with a device ``tokens`` only): the step that produced
        ``tokens`` ran over more rows than go on, and ``uids[i]``'s token
        is at ``tokens[rows[i]]`` (the scheduler's rows that go on: those
        the step before neither ended by length nor lost meanwhile).  The
        engine gathers them into this step's row order on the device, one
        fixed-shape program that is built with the decode step program and
        is none of ``step_keys``; the caller never touches the array.  None:
        ``uids[i]``'s token is ``tokens[i]``.

        Returns logits ``[max_seqs, vocab]`` as a device array WITHOUT
        host synchronisation; rows ``>= len(uids)`` are padding.  With
        ``greedy=True`` returns ``(logits, next_tokens [max_seqs])`` with
        the argmax computed INSIDE the step program, so a feedback loop is
        exactly one dispatch per token.

        The block tables and positions live on device between calls:
        the host schedules every step (KV allocation, invariant checks)
        but only uploads metadata when an allocation actually changed a
        block table — once per ``block_size`` tokens per sequence — the
        role the reference's pinned ★fast_host_buffer staging plays on
        the per-token path.  Host bookkeeping (seen_tokens) advances
        immediately.
        """
        sm = self.state_manager
        S, B = self._batch.max_seqs, self._max_blocks
        n = len(uids)
        if n > S:
            raise ValueError(f"decode_step: {n} sequences exceed max_seqs {S}")
        with open_span(self.tracer, "engine/decode_prep") as span:
            seqs, state = self._prepare_decode(uids)
            # (a model with ``step_counters``: as wide as ``next_tokens``)
            width = S + len(self.step_counters)
            tok = self._as_token_array(tokens, n, width)
            if rows is not None:    # (pad rows read row 0: never used)
                tok = self._gather_tokens(
                    tok, self._as_token_array(rows, n, width))
            if type(span) is SpanHandle:
                span.attrs = {"seqs": n}    # live rows of the S it runs
                if self._stateful:
                    span.attrs.update(self._state_counters())
                if self._grouped:
                    span.attrs.update(self._window_counters(seqs))
        try:
            with open_span(self.tracer, "engine/decode_step") as span:
                step = self._get_decode_step()
                logits, nxt, new_cache, new_pos = step(
                    self.params, sm.kv_cache.cache, state["tables"],
                    state["pos"], tok, *state["slots"])
                self._launched(span, step,
                               [(seq.seen_tokens, 1) for seq in seqs]
                               if self._counts_rows else ())
        except Exception:
            self._recover_donated_cache()
            raise
        sm.kv_cache.update(new_cache)
        if self._gather_tokens is None:
            self._build_token_gather(nxt)
        host_toks = (None if isinstance(tokens, jax.Array)
                     else [int(t) for t in tokens])
        for i, seq in enumerate(seqs):
            seq.seen_tokens += 1
            if host_toks is not None:
                sm.record_fed_tokens(seq, host_toks[i:i + 1])
                sm.register_prefix(seq)
            # else the values are still on the device: the caller reports
            # them (record_device_tokens), or registration stops at the
            # next full block, whose tokens the host then lacks
        # device positions advanced in lockstep with seen_tokens
        self._dev_decode_state = {
            "tables": state["tables"], "pos": new_pos,
            "slots": state["slots"],
            "key": (tuple(uids), tuple(s.seen_tokens for s in seqs))}
        if greedy:
            return logits, nxt
        return logits

    def counters_of(self, next_tokens) -> Dict[str, int]:
        """What the model counted on the device in the step whose fetched
        ``next_tokens`` vector this is (``step_counters``: name -> value);
        {} for a model that states none."""
        names = self.step_counters
        return {name: int(v) for name, v in zip(
            names, next_tokens[len(next_tokens) - len(names):])}

    def record_device_tokens(self, uids: Sequence[int],
                             tokens: Sequence[int]) -> None:
        """The values of the tokens the last :meth:`decode_step` fed
        ``uids`` as a device array, now that the host has fetched them: the
        sequences' token records catch up with ``seen_tokens`` and their
        full blocks enter the prefix cache, as after a step fed from the
        host.  A uid flushed in between is skipped."""
        sm = self.state_manager
        for uid, tok in zip(uids, tokens):
            seq = sm.get_sequence(uid)
            if seq is not None:
                sm.record_fed_tokens(seq, (tok,))
                sm.register_prefix(seq)

    def _prepare_decode(self, uids):
        """The host's share of a decode step before its dispatch: one KV
        slot per sequence (a new block when the last one is full) and,
        only when an allocation changed a table or the batch is not the
        one the device state describes, the upload of tables and
        positions.  Returns ``(sequences, device state)``."""
        sm = self.state_manager
        max_context = self.config.state_manager.max_context
        seqs = []
        tables_changed = False
        if self._grouped:
            # (a release alone uploads nothing: the entries it leaves in
            # the device's table lie below every later row's band, where
            # no read goes, and turn to trash at the next upload)
            sm.release_windows()
        for uid in uids:
            seq = sm.get_sequence(uid)
            if seq is None or seq.pending:
                raise RuntimeError(
                    f"decode_step: sequence {uid} missing or has pending "
                    f"prompt tokens — run put() first")
            if seq.seen_tokens + 1 > max_context:
                raise RuntimeError(
                    f"decode_step: sequence {uid} would exceed max_context")
            before = len(seq.blocks), len(seq.win_blocks)
            sm.maybe_allocate_kv(seq, 1)
            tables_changed |= (len(seq.blocks),
                               len(seq.win_blocks)) != before
            seqs.append(seq)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
            RAGGED_DEBUG, validate_ragged_metadata, validate_window_tables)

        if RAGGED_DEBUG:
            validate_ragged_metadata(seqs, [np.empty(1)] * len(seqs),
                                     sm.block_size)
            if self._grouped:
                validate_window_tables(seqs, [np.empty(1)] * len(seqs),
                                       sm.block_size, sm.window)
        state = self._dev_decode_state
        key = (tuple(uids), tuple(s.seen_tokens for s in seqs))
        if state is None or tables_changed or state["key"] != key:
            state = self._upload_decode_state(seqs, key)
        return seqs, state

    def _require(self, feature: str, path: str) -> None:
        """``state_manager.require`` with the model's name in the path."""
        self.state_manager.require(
            feature, f"{path} of {type(self.model).__name__}")

    def _window_counters(self, single_rows, chunks=()) -> Dict[str, int]:
        """The window group's counters on a dispatch's span: its pool's
        blocks and those held now, those released since the span before
        (``release_window``), and what this dispatch's reads must do in
        each kind of layer.  Its one-token rows (``single_rows``: their
        sequences): ``read_blocks``, the table blocks they hold up to the
        position they feed (every global layer reads them), and
        ``read_blocks_win``, those of them inside the band, times the
        window layers.  Its longer chunks (``chunks``: (start, tokens)):
        ``attn_pairs``, their causal (query, key) pairs (a global layer's),
        ``attn_pairs_win``, the pairs inside the band (a window layer's)
        and ``ctx_rows_win``, the cached rows inside it."""
        sm = self.state_manager
        bs, win = sm.block_size, sm.window
        alloc = sm.win_allocator
        released, self._win_reported = \
            sm.win_released - self._win_reported, sm.win_released
        out = {
            "win_pool_blocks": alloc.num_blocks - 1,
            "win_blocks_held": alloc.num_blocks - 1 - alloc.free_blocks,
            "win_blocks_released": released,
            "read_blocks": sum(s.seen_tokens // bs + 1 for s in single_rows),
            "read_blocks_win": len(sm.kv_cache.window_layers) * sum(
                s.seen_tokens // bs + 1 - sm._window_first(s.seen_tokens)
                for s in single_rows),
            # the KEYS of those blocks a row sees, ``min(t + 1, window)``
            # each: what a band's read needs at the row's real width
            "read_keys_win": len(sm.kv_cache.window_layers) * sum(
                min(s.seen_tokens + 1, win) for s in single_rows)}
        if chunks:
            out["attn_pairs"] = sum(n * (2 * a + n + 1) // 2
                                    for a, n in chunks)
            # a query at t sees min(t + 1, window) keys of the band: the
            # first ``ramp`` queries of a chunk see all t + 1
            ramps = [max(0, min(a + n, win - 1) - a) for a, n in chunks]
            out["attn_pairs_win"] = sum(
                r * (2 * a + r + 1) // 2 + (n - r) * win
                for (a, n), r in zip(chunks, ramps))
            # the rows a chunk's queries see at all: its own and the
            # ``window - 1`` before its first (what an expansion of the
            # band alone expands)
            out["ctx_rows_win"] = sum(min(a, win - 1) + n
                                      for a, n in chunks)
        return out

    def _chunk_step_counters(self, chunks, tiles: int,
                             tile: int) -> Dict[str, int]:
        """How the tiled chunk read's grid fits what this batch's chunks
        (``chunks``: (start, tokens), in ``tiles`` tiles of the bucket) can
        see, on the ``engine/build_batch`` span: ``chunk_key_steps``, the key
        steps the grid of ``paged_prefill_attention`` runs, summed over
        tiles, attention layers and passes, and ``chunk_live_key_steps``,
        those of them that hold a visible key.  A model with window layers
        adds the window layers' share of each (``..._win``).  Host
        arithmetic on the kernel's own rule (``prefill_key_steps``)."""
        from deepspeed_tpu.inference.v2.kernels import prefill_key_steps

        sm, cfg = self.state_manager, self.model.config
        cache = sm.kv_cache
        shape = dict(group=cfg.num_attention_heads // self.model.num_kv_heads,
                     block_size=sm.block_size, entries=self._max_blocks,
                     tile_q=tile)
        layers = {getattr(cfg, "sliding_window", None):
                  len(cache.kv_layers) * self._passes}
        if self._grouped:
            layers = {None: len(cache.kv_layers) - len(cache.window_layers),
                      sm.window: len(cache.window_layers)}
        out = dict.fromkeys(("chunk_key_steps", "chunk_live_key_steps"), 0)
        for window, count in layers.items():
            steps, live = prefill_key_steps(chunks, tiles, window=window,
                                            **shape)
            out["chunk_key_steps"] += count * steps
            out["chunk_live_key_steps"] += count * live
            if self._grouped and window is not None:
                out.update(chunk_key_steps_win=count * steps,
                           chunk_live_key_steps_win=count * live)
        return out

    def index_counters(self, single_rows, chunks=()) -> Dict[str, int]:
        """What a learned sparse-attention indexer (the model's
        ``index_topk``) must do for one dispatch, a layer: its one-token
        rows (``single_rows``: the position each feeds) score ``idx_keys``
        cached positions (``p + 1`` each) and read ``sel_keys`` of them
        (``min(p + 1, index_topk)``); the rows of its longer chunks
        (``chunks``: (start, tokens)) ``idx_pairs`` and ``sel_pairs``, the
        same two sums.  Host arithmetic on the lengths alone (the serving
        scheduler counts a consumed decode step's rows by it too)."""
        k = self.index_topk
        out = {"idx_keys": sum(p + 1 for p in single_rows),
               "sel_keys": sum(min(p + 1, k) for p in single_rows)}
        if chunks:
            out["idx_pairs"] = sum(n * (2 * a + n + 1) // 2
                                   for a, n in chunks)
            # the first max(0, k - a) rows of a chunk see all p + 1
            ramps = [max(0, min(a + n, k) - a) for a, n in chunks]
            out["sel_pairs"] = sum(
                r * (2 * a + r + 1) // 2 + (n - r) * k
                for (a, n), r in zip(chunks, ramps))
        return out

    def _latent_step_counters(self, chunks, tiles: int,
                              tile: int) -> Dict[str, int]:
        """``_chunk_step_counters`` for an engine with a latent pool row:
        ``latent_key_steps``, the key steps the grid of
        ``latent_prefill_attention`` runs, summed over tiles and layers, and
        ``latent_live_key_steps``, those of them that hold a visible key
        (``latent_prefill_key_steps``, the kernel's own rule)."""
        from deepspeed_tpu.inference.v2.kernels import \
            latent_prefill_key_steps

        sm = self.state_manager
        steps, live = latent_prefill_key_steps(
            chunks, tiles, block_size=sm.block_size,
            entries=self._max_blocks, tile_q=tile)
        layers = len(sm.kv_cache.kv_layers) * self._passes
        return {"latent_key_steps": layers * steps,
                "latent_live_key_steps": layers * live}

    def _sparse_step_counters(self, chunks, tiles: int,
                              tile: int) -> Dict[str, int]:
        """What the tile rows' sparse read (``sparse_tile_read``) walks for
        one dispatch, a layer: ``sparse_key_steps``, the key steps its
        tiles' tables hold, and ``sparse_live_key_steps``, those of them at
        or before a tile's last position, the ones that do work
        (``sparse_tile_key_steps``, the kernel's own rule)."""
        from deepspeed_tpu.inference.v2.kernels.sparse_latent import \
            sparse_tile_key_steps

        steps, live = sparse_tile_key_steps(
            chunks, tiles, block_size=self.state_manager.block_size,
            entries=self._max_blocks, tile_q=tile)
        return {"sparse_key_steps": steps, "sparse_live_key_steps": live}

    def _recover_donated_cache(self) -> None:
        """A jitted step that donates the KV cache raised after donation
        — the cache may reference consumed arrays and its content is
        unrecoverable.  Drop the cached decode state, reallocate a
        zeroed cache, and flush every live sequence so subsequent calls
        start clean instead of passing deleted buffers.  Shared by
        :meth:`decode_step` and :meth:`verify_step` (with speculation
        enabled the verify pass IS the steady-state tick)."""
        sm = self.state_manager
        self._dev_decode_state = None
        for leaf in jax.tree_util.tree_leaves(sm.kv_cache.cache):
            if getattr(leaf, "is_deleted", lambda: False)():
                sm.kv_cache.update(jax.tree_util.tree_map(
                    jnp.zeros_like, sm.kv_cache.cache))
                sm.flush(list(sm._seqs))
                if sm.prefix_cache is not None:
                    sm.prefix_cache.clear()   # cached KV is gone too
                break

    def _as_token_array(self, tokens, n: int, S: int) -> jax.Array:
        if isinstance(tokens, jax.Array):
            tok = tokens.astype(jnp.int32)
            if tok.shape != (S,):
                tok = jnp.zeros((S,), jnp.int32).at[:n].set(tok[:n])
            return tok
        return jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, S - n)))

    def _upload_decode_state(self, seqs, key):
        tables, pos = _pack_tables_positions(seqs, self._batch.max_seqs,
                                             self._max_blocks)
        slots = ()
        if self._stateful:      # each row's state slot; pad rows: scratch
            slots = np.full((self._batch.max_seqs,),
                            self.state_manager.state_pool.scratch, np.int32)
            slots[:len(seqs)] = [s.state_slot for s in seqs]
            slots = (jnp.asarray(slots),)
        if self._grouped:       # the window group's tables
            slots += (jnp.asarray(_pack_window_tables(
                seqs, self._batch.max_seqs, self._max_blocks)),)
        state = {"tables": jnp.asarray(tables), "pos": jnp.asarray(pos),
                 "slots": slots, "key": key}
        self._dev_decode_state = state
        return state

    def _get_decode_step(self):
        key = ("decode_step",)
        runner = self._steps.get(key)
        if runner is not None:
            return runner
        B = self._max_blocks
        bs = self.state_manager.block_size

        # what ``_upload_decode_state`` appends: the state slots (a model
        # with recurrent state), the window group's tables (kv_groups)
        names = ("state_slot",) * self._stateful \
            + ("tables_win",) * self._grouped

        S = self._batch.max_seqs

        def run(params, cache, tables, pos, tok, *slots):
            if self.step_counters:  # fed a step's tokens, counters behind
                tok = tok[:S]
            batch = _device_decode_batch(tables, pos, tok, bs, B,
                                         **dict(zip(names, slots)))
            logits, new_cache, *counts = self.model(params, cache, batch,
                                                    decode=True)
            with jax.named_scope("sample_argmax"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if counts:              # (``step_counters``)
                    nxt = jnp.concatenate([nxt, counts[0]])
            return logits, nxt, new_cache, pos + 1

        runner = jax.jit(_named(run, "decode_step"), donate_argnums=(1, 3))
        self._steps[key] = runner
        return runner

    def _build_token_gather(self, nxt: jax.Array) -> None:
        """``decode_step``'s reorder of a step's tokens into the rows of the
        step after it (``rows``): built right after the first execution of
        the step program and compiled on that execution's own ``nxt``, so
        that the placement it is compiled for is the one it will meet and
        no later tick builds a program.  No step program: not in
        ``_steps``."""
        self._gather_tokens = jax.jit(_named(
            lambda tok, rows: tok[rows], "gather_tokens"))
        self._gather_tokens(nxt, self._as_token_array((), 0, nxt.shape[0]))

    # ------------------------------------------------------------------ #
    # Speculative decoding: multi-token verify (ROADMAP item 1).  One
    # weight pass scores K candidate positions per sequence — the fed
    # token plus K-1 drafted lookahead tokens — and returns ALL K logits
    # rows, so the caller's sampler can accept the longest matching draft
    # prefix plus one bonus/correction token.  KV for every fed token is
    # written at its position; rejected lookahead rows are either
    # overwritten by the next real feed at that position (never attended
    # before then — the causal mask stops at each token's own position)
    # or, when they spilled into freshly allocated lookahead blocks,
    # rolled back by commit_verified's block trim.
    # ------------------------------------------------------------------ #
    def verify_step(self, uids: Sequence[int],
                    tokens: Sequence[Sequence[int]],
                    greedy: bool = False):
        """Score ``tokens[i]`` (K fed tokens for ``uids[i]``: its next
        input token followed by K-1 drafts) in ONE forward.

        Every row must have the same length K (one compiled program per
        K).  Each sequence must be live with no pending prompt tokens.
        Neither ``seen_tokens`` nor the host token record advances here —
        the caller decides acceptance from the returned logits and then
        calls :meth:`commit_verified` with the accepted feed prefix.

        Returns logits ``[max_seqs, K, vocab]`` as a device array
        WITHOUT host synchronisation (rows ``>= len(uids)`` are
        padding): row ``[i, k]`` is the distribution after consuming
        ``tokens[i][:k+1]`` — identical (up to kernel rounding;
        bit-exact on the f32 CPU path) to what K sequential
        :meth:`decode_step` calls would return while the drafts match.

        ``greedy=True`` returns ``(logits, next_tokens [max_seqs, K])``
        with the argmax computed INSIDE the step program — an all-greedy
        caller fetches K ints per sequence instead of K vocab rows
        (the same asymmetry :meth:`decode_step`'s greedy mode exploits).
        """
        self._require("verify", "verify_step")
        sm = self.state_manager
        S, B = self._batch.max_seqs, self._max_blocks
        n = len(uids)
        if n == 0 or n != len(tokens):
            raise ValueError(
                f"verify_step: {n} uids but {len(tokens)} token rows")
        K = len(tokens[0])
        if K < 1 or any(len(t) != K for t in tokens):
            raise ValueError(
                "verify_step: all rows must share one draft length K >= 1")
        if n > S:
            raise ValueError(f"verify_step: {n} sequences exceed "
                             f"max_seqs {S}")
        max_context = self.config.state_manager.max_context
        seqs = []
        for uid in uids:
            seq = sm.get_sequence(uid)
            if seq is None or seq.pending:
                raise RuntimeError(
                    f"verify_step: sequence {uid} missing or has pending "
                    f"prompt tokens — run put() first")
            if seq.seen_tokens + K > max_context:
                raise RuntimeError(
                    f"verify_step: sequence {uid} would exceed "
                    f"max_context {max_context} with {K} lookahead slots")
            sm.maybe_allocate_kv(seq, K)      # K lookahead KV slots
            seqs.append(seq)

        tables, pos = _pack_tables_positions(seqs, S, B)
        tok = np.zeros((S, K), np.int32)
        tok[:n] = np.asarray([[int(t) for t in row] for row in tokens],
                             np.int32)
        packed = jnp.asarray(np.concatenate(
            [tables.ravel(), pos, tok.ravel()]))       # ONE upload
        try:
            with open_span(self.tracer, "engine/verify_step") as span:
                step = self._get_verify_step(K)
                logits, nxt, new_cache = step(
                    self.params, sm.kv_cache.cache, packed)
                self._launched(span, step)
        except Exception:
            # same donated-cache hazard as decode_step: with speculation
            # on, THIS is the steady-state tick, so it needs the same
            # clean-reset path
            self._recover_donated_cache()
            raise
        sm.kv_cache.update(new_cache)
        # lookahead positions moved under any cached decode tables
        self._dev_decode_state = None
        if greedy:
            return logits, nxt
        return logits

    def commit_verified(self, uid: int,
                        accepted_tokens: Sequence[int]) -> None:
        """Advance ``uid`` past the accepted prefix of its last
        :meth:`verify_step` feed (KV for those tokens is already
        written), and ROLL BACK the rejected lookahead: blocks allocated
        past what ``seen_tokens`` now needs are freed, so the allocator
        and refcounts end exactly where a never-drafted run would be.
        Accepted draft tokens are recorded host-side and full blocks
        register into the radix prefix cache as warm blocks, same as any
        other fed token."""
        sm = self.state_manager
        seq = sm.get_sequence(uid)
        if seq is None:
            raise ValueError(f"commit_verified: unknown sequence {uid}")
        a = len(accepted_tokens)
        if a < 1:
            raise ValueError(
                "commit_verified: at least the fed input token is always "
                "accepted (verify emits >= 1 token)")
        sm.record_fed_tokens(seq, accepted_tokens)
        seq.seen_tokens += a
        need = -(-seq.seen_tokens // sm.block_size)
        if len(seq.blocks) > need:
            sm.allocator.free(seq.blocks[need:])
            del seq.blocks[need:]
        sm.register_prefix(seq)
        self._dev_decode_state = None

    def _get_verify_step(self, k_tokens: int):
        key = ("verify_step", k_tokens)
        runner = self._steps.get(key)
        if runner is not None:
            return runner
        S, B = self._batch.max_seqs, self._max_blocks
        bs = self.state_manager.block_size
        # verify_k is a perf hint (TPU kernel routing); models without
        # the parameter still score verify batches correctly through
        # their generic ragged attention path
        import inspect

        try:
            accepts_k = "verify_k" in inspect.signature(
                self.model.__call__).parameters
        except (TypeError, ValueError):
            accepts_k = False
        kwargs = {"verify_k": k_tokens} if accepts_k else {}

        def run(params, cache, packed):
            tables = packed[:S * B].reshape(S, B)
            pos = packed[S * B:S * B + S]
            tok = packed[S * B + S:].reshape(S, k_tokens)
            batch = _device_verify_batch(tables, pos, tok, bs, B, k_tokens)
            logits, new_cache = self.model(params, cache, batch, **kwargs)
            logits = logits.reshape(S, k_tokens, -1)
            with jax.named_scope("sample_argmax"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, nxt, new_cache

        runner = jax.jit(_named(run, f"verify_step_K{k_tokens}"),
                         donate_argnums=(1,))
        self._steps[key] = runner
        return runner

    # ------------------------------------------------------------------ #
    # Device-resident greedy decode (TPU-native: the per-put() decode path
    # pays host<->device round-trips per token — metadata upload, dispatch,
    # logits download.
    # decode_loop runs K decode iterations as ONE lax.scan program with
    # on-device argmax and on-device metadata advance: positions increment
    # and kv write targets are derived from the block table inside the
    # program, so the host is only involved once per K tokens.)
    # ------------------------------------------------------------------ #
    #: scan-length buckets for decode_loop: arbitrary ``steps`` decomposes
    #: into at most a handful of compiled programs (greedy largest-first),
    #: instead of one recompile per distinct max_new_tokens
    DECODE_CHUNKS = (64, 16, 4, 1)

    def decode_loop(self, uids: Sequence[int], tokens: Sequence[int],
                    steps: int) -> np.ndarray:
        """Greedy-decode ``steps`` tokens for live sequences.

        ``tokens[i]`` is sequence ``uids[i]``'s next input token (e.g. the
        argmax of the logits ``put`` just returned). Returns the generated
        tokens ``[len(uids), steps]`` (the first column is the token AFTER
        consuming ``tokens``). Bookkeeping (seen_tokens) is advanced.

        Internally runs scan chunks drawn from :data:`DECODE_CHUNKS` so the
        set of compiled programs is bounded regardless of ``steps``.
        """
        self._require("decode_loop", "decode_loop")
        if len(tokens) != len(uids):
            raise ValueError(
                f"decode_loop: {len(uids)} uids but {len(tokens)} tokens")
        if len(uids) > self._batch.max_seqs:
            raise ValueError(
                f"decode_loop: {len(uids)} sequences exceed max_seqs "
                f"{self._batch.max_seqs}")
        max_context = self.config.state_manager.max_context
        for uid in uids:
            seq = self.state_manager.get_sequence(uid)
            if seq is None or seq.pending:
                raise RuntimeError(
                    f"decode_loop: sequence {uid} missing or has pending "
                    f"prompt tokens — run put() first")
            if seq.seen_tokens + steps > max_context:
                raise RuntimeError(
                    f"decode_loop: sequence {uid} would exceed max_context")
        outs = []
        cur = list(tokens)
        remaining = steps
        while remaining:
            k = next(c for c in self.DECODE_CHUNKS if c <= remaining)
            toks = self._decode_chunk(uids, cur, k)    # [n, k]
            outs.append(toks)
            cur = [int(t) for t in toks[:, -1]]
            remaining -= k
        return np.concatenate(outs, axis=1)

    def _decode_chunk(self, uids, tokens, steps: int) -> np.ndarray:
        sm = self.state_manager
        S, B = self._batch.max_seqs, self._max_blocks
        seqs = []
        for uid in uids:
            seq = sm.get_sequence(uid)
            sm.maybe_allocate_kv(seq, steps)
            seqs.append(seq)
        from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
            RAGGED_DEBUG, validate_ragged_metadata)

        if RAGGED_DEBUG:
            validate_ragged_metadata(
                seqs, [np.empty(steps)] * len(seqs), sm.block_size)

        tables, pos0 = _pack_tables_positions(seqs, S, B)
        tok0 = np.zeros((S,), np.int32)
        tok0[:len(tokens)] = np.asarray([int(t) for t in tokens], np.int32)
        packed = jnp.asarray(np.concatenate(
            [tables.ravel(), pos0, tok0]))         # ONE upload
        runner = self._get_decode_loop(steps)
        out_tokens, new_cache = runner(self.params, sm.kv_cache.cache,
                                       packed)
        sm.kv_cache.update(new_cache)
        result = np.asarray(jax.device_get(out_tokens)).T[:len(uids)]
        for i, seq in enumerate(seqs):
            # KV was written for the fed token plus all but the last
            # generated one — their values are on host now
            sm.record_fed_tokens(
                seq, [int(tokens[i])] + result[i][:-1].tolist())
            seq.seen_tokens += steps
            sm.register_prefix(seq)
        return result

    def _get_decode_loop(self, steps: int):
        key = ("decode_loop", steps)
        runner = self._steps.get(key)
        if runner is not None:
            return runner
        S, B = self._batch.max_seqs, self._max_blocks
        bs = self.state_manager.block_size

        def run(params, cache, packed):
            tables = packed[:S * B].reshape(S, B)
            pos0 = packed[S * B:S * B + S]
            tok0 = packed[S * B + S:]

            def body(carry, _):
                kv, tok, pos = carry
                batch = _device_decode_batch(tables, pos, tok, bs, B)
                logits, kv, *_ = self.model(params, kv, batch, decode=True)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (kv, nxt, pos + 1), nxt

            (kv, _, _), toks = jax.lax.scan(
                body, (cache, tok0, pos0), None, length=steps)
            return toks, kv                        # toks: [steps, S]

        runner = jax.jit(run, donate_argnums=(1,))
        self._steps[key] = runner
        return runner

    # ------------------------------------------------------------------ #
    # Observability: compile-time memory ledger + live occupancy
    # ------------------------------------------------------------------ #
    def occupancy(self) -> Dict[str, float]:
        """Live ``observability/kv_*`` + ``observability/hbm_*`` gauges
        — host-side bookkeeping only (allocator free lists, refcounts,
        ``seen_tokens``, static geometry arithmetic): safe to scrape
        between steady-state decode ticks without a recompile or a host
        sync (TraceGuard-asserted in tier-1)."""
        from deepspeed_tpu.observability.memory import (hbm_footprint,
                                                        kv_occupancy)

        out = kv_occupancy(self.state_manager)
        # weights only: kv_occupancy already carries the pool bytes —
        # the same quantity must not scrape under two names
        out.update(hbm_footprint(self.params))
        # what the process built, and how long this engine took to be ready
        out.update(self._build_telemetry(
            step.__name__ for step in self._steps.values()))
        return out

    def lower_step(self, key: tuple):
        """One already-built step program lowered over abstract shapes
        (nothing runs, the live cache is not donated).  ``key`` is the
        program's key in ``step_keys``: ``(buffer_rows, prefill_tile)``
        for the ragged ``put`` step, ``("decode_step",)``,
        ``("verify_step", K)`` or ``("decode_loop", steps)``."""
        S, B = self._batch.max_seqs, self._max_blocks

        def sds(a):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))

        ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        state = ((ints(S),) if self._stateful else ()) \
            + ((ints(S, B),) if self._grouped else ())
        if key == ("decode_step",):
            args = (ints(S, B), ints(S),
                    ints(S + len(self.step_counters))) + state
        elif key[0] == "verify_step":
            args = (ints(S * B + S + S * key[1]),)
        elif key[0] == "decode_loop":
            args = (ints(S * B + 2 * S),)
        else:                   # (bucket, tile): the packed metadata row
            from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
                packed_length)

            args = (ints(packed_length(key[0], S, B, self._stateful,
                                       self._grouped)),)
        return self._steps[key].lower(
            jax.tree_util.tree_map(sds, self.params),
            jax.tree_util.tree_map(sds, self.state_manager.kv_cache.cache),
            *args)

    @property
    def step_keys(self) -> List[tuple]:
        """Keys of the step programs built so far (see ``lower_step``)."""
        return list(self._steps)

    def capture_memory_ledger(self, ledger=None):
        """HLO memory ledger of the steady-state decode program: lower +
        compile ``decode_step`` over abstract shapes (no execution, no
        donation of the LIVE cache) and record ``memory_analysis()`` /
        ``cost_analysis()``.  Backends without the analysis yield an
        explicit ``unavailable`` record."""
        from deepspeed_tpu.observability.memory import MemoryLedger

        led = ledger if ledger is not None else MemoryLedger()
        sm = self.state_manager
        meta = {"max_seqs": self._batch.max_seqs,
                "kv_blocks": sm.allocator.num_blocks,
                "block_size": sm.block_size}
        try:
            self._get_decode_step()
            compiled = self.lower_step(("decode_step",)).compile()
        except Exception as e:  # noqa: BLE001 — absence is a record
            led.record_unavailable("decode_step",
                                   f"{type(e).__name__}: {e}", meta=meta)
            return led
        led.record("decode_step", compiled, meta=meta)
        return led

    # ------------------------------------------------------------------ #
    # flush (reference engine_v2.py:210)
    # ------------------------------------------------------------------ #
    def flush(self, uids: Sequence[int]) -> None:
        self.state_manager.flush(uids)
        # freed blocks may be re-allocated: device-resident decode tables
        # are stale the moment a sequence is flushed
        self._dev_decode_state = None

    # ------------------------------------------------------------------ #
    # Preemption support (the serving scheduler's KV-pressure path):
    # flush_to_host releases a sequence's device blocks but hands back a
    # host snapshot, and resume() re-admits by RECOMPUTE — re-prefilling
    # the full token history the caller kept host-side.  The engine never
    # stores token ids (they only pass through ``pending``), so the
    # snapshot carries bookkeeping, not tokens; under greedy decoding the
    # recomputed KV is bit-identical in effect and generation continues
    # token-for-token as if never preempted.
    # ------------------------------------------------------------------ #
    def flush_to_host(self, uids: Sequence[int],
                      include_kv: bool = False) -> Dict[int, Dict[str, Any]]:
        """Release device KV for ``uids`` (preemption).  Returns per-uid
        host snapshots ``{"seen_tokens", "pending_tokens"}`` — the caller
        owns the token history and re-admits via :meth:`resume`.

        ``include_kv=True`` additionally gathers each sequence's actual
        KV rows to the host (``"kv"``: a per-layer ``{"k"/"v"}`` tree of
        ``[blocks * block_size, ...]`` arrays, rows as the pool stores them,
        in block-table order) so
        another engine over the same model can :meth:`resume` WITHOUT the
        recompute re-prefill — the disaggregated prefill→decode handoff."""
        if include_kv:
            self._require("kv_handoff", "flush_to_host(include_kv=True)")
        out: Dict[int, Dict[str, Any]] = {}
        for uid in uids:
            seq = self.state_manager.get_sequence(uid)
            if seq is None:
                raise ValueError(f"flush_to_host: unknown sequence {uid}")
            snap: Dict[str, Any] = {"seen_tokens": seq.seen_tokens,
                                    "pending_tokens": len(seq.pending)}
            if include_kv and seq.blocks:
                snap["kv"] = self.state_manager.kv_cache.gather_blocks(
                    seq.blocks)
                snap["block_size"] = self.state_manager.block_size
            out[uid] = snap
        self.flush(uids)
        return out

    def resume(self, uid: int, tokens: Sequence[int], sync: bool = True,
               kv_state: Optional[Dict[str, Any]] = None
               ) -> Dict[int, np.ndarray]:
        """Re-admit a flushed sequence.  The sequence must not be live
        (it was flushed by :meth:`flush_to_host`).

        Without ``kv_state``: recompute — re-prefill the full token
        history (prompt + tokens generated before preemption) and return
        the last token's logits, exactly as :meth:`put` would.

        With ``kv_state`` (a :meth:`flush_to_host(include_kv=True)`
        snapshot, possibly from ANOTHER engine of identical geometry):
        allocate fresh blocks, scatter the carried KV rows in, and mark
        ``tokens[:seen_tokens]`` as already seen — no recompute.  Only
        the tail ``tokens[seen_tokens:]`` (if any) runs through
        :meth:`put`; when the tail is empty the return is ``{}`` and the
        next :meth:`decode_step`/``put`` feeds from position
        ``seen_tokens``."""
        sm = self.state_manager
        if sm.get_sequence(uid) is not None:
            raise RuntimeError(
                f"resume: sequence {uid} is still live — it was never "
                f"flushed, or the uid was reused")
        if kv_state is None or "kv" not in kv_state:
            return self.put([uid], [tokens], sync=sync)
        self._require("kv_handoff", "resume(kv_state=...)")
        seen = int(kv_state["seen_tokens"])
        if not 0 < seen <= len(tokens):
            raise ValueError(
                f"resume: kv_state covers {seen} tokens but {len(tokens)} "
                f"token values were supplied")
        if kv_state.get("block_size", sm.block_size) != sm.block_size:
            raise ValueError(
                f"resume: kv_state block_size "
                f"{kv_state.get('block_size')} != {sm.block_size}")
        n_blocks = -(-seen // sm.block_size)
        seq = sm.get_or_create_sequence(uid)
        try:
            seq.blocks = sm._allocate(n_blocks)
            payload = kv_state["kv"]
            need_rows = n_blocks * sm.kv_cache.block_rows
            payload = jax.tree_util.tree_map(
                lambda h: np.asarray(h)[:need_rows], payload)
            sm.kv_cache.scatter_blocks(seq.blocks, payload)
        except Exception:
            if seq.blocks:
                sm.allocator.free(seq.blocks)
            del sm._seqs[uid]
            raise
        seq.seen_tokens = seen
        sm.record_fed_tokens(seq, tokens[:seen])
        sm.register_prefix(seq)
        # freshly scattered blocks invalidate any cached decode tables
        self._dev_decode_state = None
        if len(tokens) > seen:
            return self.put([uid], [list(tokens)[seen:]], sync=sync)
        return {}

    # ------------------------------------------------------------------ #
    # serialize (reference engine_v2.py:237 + flat_model_helpers.py —
    # flattened inference checkpoints: one contiguous payload + a metadata
    # manifest, so a serving replica restores with a single sequential
    # read instead of thousands of per-tensor files)
    # ------------------------------------------------------------------ #
    def serialize(self, save_path: str) -> None:
        """Write ``model.bin`` (concatenated little-endian tensor payloads)
        and ``metadata.json`` (name/shape/dtype/offset per tensor + engine
        config) under ``save_path``."""
        import json
        import os

        os.makedirs(save_path, exist_ok=True)
        flat, _ = jax.tree_util.tree_flatten_with_path(
            jax.device_get(self.params))
        manifest = []
        offset = 0
        with open(os.path.join(save_path, "model.bin"), "wb") as f:
            for path, leaf in flat:
                arr = np.ascontiguousarray(np.asarray(leaf))
                name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in path)
                manifest.append({"name": name, "shape": list(arr.shape),
                                 "dtype": arr.dtype.name, "offset": offset,
                                 "nbytes": int(arr.nbytes)})
                f.write(arr.tobytes())
                offset += arr.nbytes
        meta = {
            "format_version": 1,
            "tensors": manifest,
            "engine_config": self.config.to_dict()
            if hasattr(self.config, "to_dict") else {},
        }
        with open(os.path.join(save_path, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=1, default=str)
        log_dist(f"InferenceEngineV2: serialized {len(manifest)} tensors "
                 f"({offset/1e6:.1f} MB) to {save_path}", ranks=[0])

    @staticmethod
    def deserialize_params(save_path: str):
        """Restore the flat param dict ``{name: np.ndarray}`` from
        :meth:`serialize` output (memory-mapped, zero-copy views)."""
        import json
        import os

        with open(os.path.join(save_path, "metadata.json")) as f:
            meta = json.load(f)
        data = np.memmap(os.path.join(save_path, "model.bin"), mode="r",
                         dtype=np.uint8)
        out = {}
        for t in meta["tensors"]:
            n = int(np.prod(t["shape"])) if t["shape"] else 1
            arr = np.frombuffer(data, dtype=np.dtype(t["dtype"]), count=n,
                                offset=t["offset"]).reshape(t["shape"])
            out[t["name"]] = arr
        return out

    @classmethod
    def from_hf(cls, model_path: str,
                config: Optional[RaggedInferenceEngineConfig] = None,
                mesh=None, dtype=None, quantize_bits: Optional[int] = None,
                quantize_groups: int = 64):
        """Serve a real HuggingFace checkpoint directory (reference: the
        MII/engine_factory path that builds a FastGen engine from a HF
        snapshot).  ``model_implementations.HF_MODELS`` names the
        architectures served (llama, mistral, internlm, opt, falcon, mixtral,
        olmoe, qwen3_next, deepseek_v3, glm_moe_dsa, longcat_flash, lfm2_moe,
        afmoe, ouro,
        jamba) and which
        take
        a ``mesh`` with a non-trivial 'model' axis (the others refuse one):
        weights then land PRE-SHARDED
        by the Megatron split rules (``modules/attention.py::
        shard_ragged_params``'s specs) — no full host/device copy.

        ``quantize_bits=8``: weight-only quantized serving (reference
        ★cutlass_ops/mixed_gemm) — projection weights REST as int8
        (embeddings excepted), halving the HBM weight footprint.
        Prefill matmuls run the ops/quantized_matmul.py Pallas kernel
        (int8 tiles dequantized in VMEM); decode-sized batches take the
        grouped-dequant composition, which XLA streams efficiently at
        scale (measured 1.71x faster decode at 850M-class on v5e).
        """
        from deepspeed_tpu.checkpoint.hf_loader import (config_from_hf,
                                                        load_hf_checkpoint)
        from deepspeed_tpu.inference.v2.model_implementations import (
            HF_MODELS)

        cfg = config or RaggedInferenceEngineConfig()
        arch, mcfg = config_from_hf(model_path,
                                    dtype or jnp.bfloat16)
        block_size = cfg.kv_cache.block_size
        if arch not in HF_MODELS:
            raise ValueError(
                f"FastGen has no ragged model for architecture {arch!r}")
        model_cls, serves_model_axis = HF_MODELS[arch]
        if mesh is not None and mesh.shape.get("model", 1) <= 1:
            mesh = None
        if mesh is not None and not serves_model_axis:
            raise ValueError(
                f"{model_cls.__name__} does not support tensor parallelism "
                f"yet — pass mesh=None (weights would silently land "
                f"unsharded otherwise)")
        model = model_cls(mcfg, block_size,
                          **({"mesh": mesh} if serves_model_axis else {}))
        params = load_hf_checkpoint(
            model_path, dtype=dtype or jnp.bfloat16, mesh=mesh)
        if quantize_bits:
            if arch not in ("llama", "mistral", "internlm"):
                raise ValueError(
                    f"weight-quantized serving covers the Llama-family "
                    f"ragged models; {arch!r} still consumes plain "
                    f"kernels")
            if getattr(model, "tp", 1) > 1:
                raise ValueError(
                    "weight-quantized serving does not compose with "
                    "tensor parallelism in the v2 engine yet")
            from deepspeed_tpu.runtime.weight_quantizer import (
                WeightQuantization)

            wq = WeightQuantization(quantize_bits=quantize_bits,
                                    quantize_groups=quantize_groups)
            params, n = wq.model_quantize(params, exclude=("embed",))
            log_dist(f"InferenceEngineV2: int{quantize_bits} weight-only "
                     f"quantization on {n} matrices", ranks=[0])
        return cls(model, params, cfg)

    @classmethod
    def load_serialized(cls, save_path: str, model,
                        config: Optional[RaggedInferenceEngineConfig] = None):
        """Build an engine from a serialized checkpoint: the flat names are
        re-nested into the model's param-tree layout."""
        flat = cls.deserialize_params(save_path)
        tree: Dict[str, Any] = {}
        for name, arr in flat.items():
            node = tree
            parts = name.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = np.asarray(arr)
        return cls(model, tree, config)

    # ------------------------------------------------------------------ #
    # Convenience generation loop (the role MII plays above the reference
    # engine: repeated put() of one token per live sequence)
    # ------------------------------------------------------------------ #
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 uids: Optional[Sequence[int]] = None) -> List[np.ndarray]:
        if uids is None:
            uids = list(range(len(prompts)))
        outs: Dict[int, List[int]] = {u: [] for u in uids}
        live = list(uids)
        nxt = self.put(uids, prompts, greedy=True)
        if eos_token_id is None and max_new_tokens > 1 \
                and "decode_loop" not in self.state_manager.unserved:
            # no early-exit needed -> device-resident decode: one dispatch
            # per decode chunk instead of one per token (grouped by
            # max_seqs — decode_loop batches at most one slot per sequence)
            first = nxt
            rest: Dict[int, np.ndarray] = {}
            S = self._batch.max_seqs
            for g in range(0, len(uids), S):
                grp = list(uids[g:g + S])
                toks = self.decode_loop(grp, [first[u] for u in grp],
                                        max_new_tokens - 1)
                for i, u in enumerate(grp):
                    rest[u] = toks[i]
            self.flush(uids)
            return [np.asarray([first[u]] + rest[u].tolist(), np.int32)
                    for u in uids]
        for _ in range(max_new_tokens):
            for u in live:
                outs[u].append(nxt[u])
            live = [u for u in live
                    if not (eos_token_id is not None
                            and nxt[u] == eos_token_id)]
            if not live:
                break
            nxt = self.put(live, [[nxt[u]] for u in live], greedy=True)
        self.flush(uids)
        return [np.asarray(outs[u], np.int32) for u in uids]
