"""Ragged batch metadata (reference: inference/v2/ragged/ragged_wrapper.py
``RaggedBatchWrapper`` — token/sequence metadata staged through a pinned
host buffer ★fast_host_buffer.cu; here plain numpy arrays handed to one
jitted forward).

A ragged batch is a fixed-size token buffer (the Dynamic SplitFuse token
budget) packing tokens from up to ``max_seqs`` sequences::

    token_ids  [T] int32   padded with 0
    token_slot [T] int32   which batch slot each token belongs to (pad -> 0,
                           but pads scatter KV to the trash block)
    token_pos  [T] int32   absolute position in its sequence
    block_tables [max_seqs, max_blocks] int32  KV block ids (trash-padded)
    context_lens [max_seqs] int32  tokens valid after this forward
    logits_idx   [max_seqs] int32  index in [T] of each slot's last token
    kv_dest      [T] int32  flat pool index for each token's KV write

and, for a model with per-sequence recurrent state (``state_pool.py``)::

    state_slot  [max_seqs] int32  state slot of each batch slot's sequence
                                  (pad -> the scratch slot)
    chunk_start [max_seqs] int32  buffer row of the chunk's first token

and, for a model whose KV layers are in two groups (``kv_groups``)::

    block_tables_win [max_seqs, max_blocks] int32  the window group's
                           table: trash below the sequence's first live
                           entry and past its last
    kv_dest_win      [T] int32  flat index into the window group's pools

Chunks sit back to back in the buffer, or — after ``set_alignment``, which
the engine calls whenever its token budget is a whole number of prefill
tiles — in two segments: ``max_seqs`` rows for the chunks of one token,
then every longer chunk on a tile boundary (pad rows at position -1).
"""

from __future__ import annotations

import os
from typing import List

import numpy as np

from deepspeed_tpu.inference.v2.ragged.blocked_allocator import BlockedAllocator
from deepspeed_tpu.inference.v2.ragged.sequence_descriptor import (
    DSSequenceDescriptor,
)

TRASH = BlockedAllocator.TRASH_BLOCK

#: The paged kernel masks table slots past a sequence's length BY POSITION
#: only — corrupted sequence metadata would silently read another
#: sequence's KV. These host-side invariant checks are cheap (O(T + S*B))
#: and on by default; set DEEPSPEED_TPU_RAGGED_DEBUG=0 to skip them on a
#: hot serving path.
RAGGED_DEBUG = os.environ.get("DEEPSPEED_TPU_RAGGED_DEBUG", "1") != "0"


class RaggedMetadataError(RuntimeError):
    """A ragged batch's sequence metadata violates the paged-KV invariants."""


def validate_ragged_metadata(seqs: List[DSSequenceDescriptor],
                             chunks: List[np.ndarray],
                             block_size: int) -> None:
    """Assert the invariants the paged kernel relies on (debug mode):

    1. no two sequences own the same KV block — EXCEPT a block inside
       BOTH sequences' shared prefix region (radix prefix cache: the
       leading ``seq.shared_blocks`` blocks are read-only and
       legitimately multi-referenced);
    2. every sequence's block table covers seen_tokens + chunk (a write
       past capacity would land in another sequence's block);
    3. no KV write may target a shared block (writes start at
       ``seen_tokens``, which must clear the shared region — the state
       manager copy-on-write forks before ever violating this);
    4. no sequence owns the trash block (pad writes target it).
    """
    owned = {}
    for seq, chunk in zip(seqs, chunks):
        if seq.seen_tokens < 0:
            raise RaggedMetadataError(
                f"sequence {seq.uid}: negative seen_tokens "
                f"{seq.seen_tokens}")
        need = seq.seen_tokens + len(chunk)
        if len(seq.blocks) * block_size < need:
            raise RaggedMetadataError(
                f"sequence {seq.uid}: block table covers "
                f"{len(seq.blocks) * block_size} positions but "
                f"{need} are live — a KV write would spill into another "
                f"sequence's block")
        shared_n = getattr(seq, "shared_blocks", 0)
        if len(chunk) and seq.seen_tokens < shared_n * block_size:
            raise RaggedMetadataError(
                f"sequence {seq.uid}: write position {seq.seen_tokens} "
                f"falls inside its shared prefix "
                f"({shared_n} blocks) — a copy-on-write fork was skipped")
        for j, b in enumerate(seq.blocks):
            if b == TRASH:
                raise RaggedMetadataError(
                    f"sequence {seq.uid} owns the trash block {TRASH}")
            shared = j < shared_n
            if b in owned:
                prev_uid, prev_shared = owned[b]
                if prev_uid == seq.uid:
                    raise RaggedMetadataError(
                        f"KV block {b} listed twice in sequence "
                        f"{seq.uid}'s table — later positions would "
                        f"overwrite earlier tokens' KV")
                if not (shared and prev_shared):
                    raise RaggedMetadataError(
                        f"KV block {b} owned by both sequence {prev_uid} "
                        f"and sequence {seq.uid} outside their shared "
                        f"prefix regions — attention would read aliased "
                        f"KV")
                continue
            owned[b] = (seq.uid, shared)


def validate_window_tables(seqs: List[DSSequenceDescriptor],
                           chunks: List[np.ndarray], block_size: int,
                           window: int) -> None:
    """The same invariants for the window group's tables (debug mode): a
    sequence's live entries reach down to the first key its chunk's first
    query sees and up to the chunk's last position, no block is in two
    tables or twice in one, none is the trash block."""
    owned = {}
    for seq, chunk in zip(seqs, chunks):
        lo = max(0, seq.seen_tokens - window + 1) // block_size
        need = seq.seen_tokens + len(chunk)
        if seq.win_first > lo or \
                (seq.win_first + len(seq.win_blocks)) * block_size < need:
            raise RaggedMetadataError(
                f"sequence {seq.uid}: window table holds entries "
                f"[{seq.win_first}, {seq.win_first + len(seq.win_blocks)}) "
                f"but positions [{lo * block_size}, {need}) are live — a "
                f"read or a KV write would land in a block it does not own")
        for b in seq.win_blocks:
            if b == TRASH or b in owned:
                raise RaggedMetadataError(
                    f"window block {b} of sequence {seq.uid} is the trash "
                    f"block or already owned (by {owned.get(b)})")
            owned[b] = seq.uid


class RaggedBatchWrapper:
    def __init__(self, token_budget: int, max_seqs: int, max_blocks: int,
                 block_size: int, state_scratch: int = None,
                 window: int = None):
        #: the window group's width in tokens (a model with ``kv_groups``):
        #: the metadata then carries that group's tables and write targets
        self.window = window
        #: the scratch slot of the model's recurrent-state pool, or None
        #: for a model without one (no state fields in the metadata then)
        self.state_scratch = state_scratch
        self.token_budget = token_budget
        self.max_seqs = max_seqs
        self.max_blocks = max_blocks
        self.block_size = block_size
        self.clear()

    def clear(self):
        self._seqs: List[DSSequenceDescriptor] = []
        self._chunks: List[np.ndarray] = []
        self._starts: List[int] = []
        self._tokens_used = 0
        self._align = 0
        self._singles = 0
        self._tiled_used = 0

    def set_alignment(self, align: int) -> None:
        """Lay the batch out in two segments (the tiled prefill kernel's
        contract).  Rows ``[0, max_seqs)`` are the single-token segment:
        every chunk of exactly one token (the decodes of a mixed tick, a
        prompt's one-token tail) takes one row there.  Behind it, in the
        tiled segment, every longer chunk starts on an ``align`` boundary,
        so each [align]-row stripe is single-sequence — a prompt's last
        chunk may be shorter than a tile.  Pad rows of both segments carry
        position -1.  Call right after clear().  Alignment padding is rows,
        not tokens: the token budget still counts real tokens, and the
        tiled segment holds at most ``token_budget`` rows."""
        if self._seqs:
            raise RuntimeError("set_alignment before inserting sequences")
        self._align = int(align)

    @property
    def current_tokens(self) -> int:
        """Real (unpadded) tokens scheduled so far."""
        return self._tokens_used

    @property
    def tiled_rows(self) -> int:
        """Rows the tiled segment needs, whole tiles (0 when unaligned or
        when every chunk is a single token)."""
        return self._tile_up(self._tiled_used)

    @property
    def current_sequences(self) -> int:
        return len(self._seqs)

    def _tile_up(self, rows: int) -> int:
        a = max(self._align, 1)
        return -(-rows // a) * a

    def fit(self, n_tokens: int) -> int:
        """How many of a sequence's ``n_tokens`` pending tokens its chunk
        may take now (Dynamic SplitFuse); 0 when the batch has no room."""
        if len(self._seqs) >= self.max_seqs:
            return 0
        n = min(n_tokens, self.token_budget - self._tokens_used)
        if self._align > 1 and n > 1:
            # a longer chunk starts on the next tile boundary
            n = min(n, self.token_budget - self.tiled_rows)
        return n

    def can_fit(self, n_tokens: int) -> bool:
        return self.fit(n_tokens) == n_tokens

    def insert_sequence(self, seq: DSSequenceDescriptor,
                        tokens: np.ndarray) -> None:
        """reference ``insert_sequence``: add one sequence's chunk."""
        n = len(tokens)
        if not self.can_fit(n):
            raise RuntimeError("ragged batch full")
        if self._align <= 1:
            start = self._tokens_used
        elif n == 1:
            start = self._singles
            self._singles += 1
        else:
            start = self.max_seqs + self.tiled_rows
            self._tiled_used = start - self.max_seqs + n
        self._seqs.append(seq)
        self._chunks.append(np.asarray(tokens, np.int32))
        self._starts.append(start)
        self._tokens_used += n

    def finalize(self, token_capacity: int = None):
        """Build the device metadata (reference ``finalize``: host->device
        copy of the packed descriptors).

        ``token_capacity`` sizes the token-dim arrays (defaults to the full
        budget) — the engine passes the active BUCKET so a decode step
        compiles to a small program instead of the prefill-sized one.
        """
        T = token_capacity if token_capacity is not None else self.token_budget
        rows = (self.max_seqs + self.tiled_rows if self._align > 1
                else self._tokens_used)
        if rows > T:
            raise ValueError(
                f"finalize: {rows} scheduled rows exceed token capacity {T}")
        if RAGGED_DEBUG:
            validate_ragged_metadata(self._seqs, self._chunks,
                                     self.block_size)
            if self.window is not None:
                validate_window_tables(self._seqs, self._chunks,
                                       self.block_size, self.window)
        S, B = self.max_seqs, self.max_blocks
        bs = self.block_size
        token_ids = np.zeros((T,), np.int32)
        token_slot = np.zeros((T,), np.int32)
        # two-segment mode: pads carry position -1 so the kernels and the
        # XLA path mask them out
        token_pos = np.full((T,), -1 if self._align > 1 else 0, np.int32)
        kv_dest = np.full((T,), TRASH * bs, np.int32)  # pads -> trash block
        block_tables = np.full((S, B), TRASH, np.int32)
        context_lens = np.zeros((S,), np.int32)
        logits_idx = np.zeros((S,), np.int32)
        n_valid = len(self._seqs)
        if self.window is not None:
            tables_win = np.full((S, B), TRASH, np.int32)
            kv_dest_win = np.full((T,), TRASH * bs, np.int32)

        for slot, (seq, chunk, cursor) in enumerate(
                zip(self._seqs, self._chunks, self._starts)):
            n = len(chunk)
            pos = np.arange(seq.seen_tokens, seq.seen_tokens + n, dtype=np.int32)
            token_ids[cursor:cursor + n] = chunk
            token_slot[cursor:cursor + n] = slot
            token_pos[cursor:cursor + n] = pos
            blocks = np.asarray(seq.blocks, np.int32)
            if len(blocks) > B:
                raise RuntimeError(
                    f"sequence {seq.uid} exceeds max_blocks {B}")
            block_tables[slot, :len(blocks)] = blocks
            kv_dest[cursor:cursor + n] = blocks[pos // bs] * bs + pos % bs
            if self.window is not None:
                row = tables_win[slot]
                seq.write_window_row(row)
                kv_dest_win[cursor:cursor + n] = row[pos // bs] * bs + pos % bs
            context_lens[slot] = seq.seen_tokens + n
            logits_idx[slot] = cursor + n - 1

        meta = {
            "token_ids": token_ids, "token_slot": token_slot,
            "token_pos": token_pos, "kv_dest": kv_dest,
            "block_tables": block_tables, "context_lens": context_lens,
            "logits_idx": logits_idx, "n_valid": np.int32(n_valid),
        }
        if self.state_scratch is not None:
            meta["state_slot"] = np.full((S,), self.state_scratch, np.int32)
            meta["chunk_start"] = np.zeros((S,), np.int32)
            meta["state_slot"][:n_valid] = [s.state_slot for s in self._seqs]
            meta["chunk_start"][:n_valid] = self._starts
        if self.window is not None:
            meta["block_tables_win"] = tables_win
            meta["kv_dest_win"] = kv_dest_win
        return meta

    @property
    def sequences(self) -> List[DSSequenceDescriptor]:
        return list(self._seqs)

    @property
    def chunk_sizes(self) -> List[int]:
        return [len(c) for c in self._chunks]

    @property
    def starts(self) -> List[int]:
        """Buffer row of each chunk's first token."""
        return list(self._starts)


# --------------------------------------------------------------------- #
# Metadata packing: ONE int32 host->device transfer per forward instead of
# seven (each upload is its own dispatch; the reference stages through
# one pinned fast_host_buffer for the same reason)
# --------------------------------------------------------------------- #
_META_FIELDS = ("token_ids", "token_slot", "token_pos", "kv_dest",
                "block_tables", "context_lens", "logits_idx")
#: appended for a model with recurrent state
_STATE_FIELDS = ("state_slot", "chunk_start")
#: appended for a model with two groups of KV layers
_WIN_FIELDS = ("block_tables_win", "kv_dest_win")


def _fields(state, win=False):
    return _META_FIELDS + (_STATE_FIELDS if state else ()) \
        + (_WIN_FIELDS if win else ())


def pack_metadata(meta) -> np.ndarray:
    """Flatten the finalize() dict into one int32 vector (host side)."""
    return np.concatenate(
        [np.asarray(meta[k], np.int32).ravel()
         for k in _fields("state_slot" in meta, "kv_dest_win" in meta)])


def packed_length(token_capacity: int, max_seqs: int, max_blocks: int,
                  state: bool = False, win: bool = False) -> int:
    """Length of the packed vector of one batch."""
    return (5 if win else 4) * token_capacity \
        + (2 if win else 1) * max_seqs * max_blocks \
        + (4 if state else 2) * max_seqs


def unpack_metadata(packed, token_capacity: int, max_seqs: int,
                    max_blocks: int, state: bool = False,
                    win: bool = False):
    """Rebuild the batch dict from the packed vector (inside jit)."""
    T, S, B = token_capacity, max_seqs, max_blocks
    sizes = {"token_ids": (T, (T,)), "token_slot": (T, (T,)),
             "token_pos": (T, (T,)), "kv_dest": (T, (T,)),
             "block_tables": (S * B, (S, B)),
             "context_lens": (S, (S,)), "logits_idx": (S, (S,)),
             "state_slot": (S, (S,)), "chunk_start": (S, (S,)),
             "block_tables_win": (S * B, (S, B)), "kv_dest_win": (T, (T,))}
    out = {}
    o = 0
    for k in _fields(state, win):
        n, shape = sizes[k]
        out[k] = packed[o:o + n].reshape(shape)
        o += n
    return out
