"""The depthwise causal convolution of the families that keep a
convolution's tail per sequence in a state slot (``ragged/state_pool.py``):
the Gated DeltaNet layers of Qwen3-Next and Olmo-Hybrid, LFM2's gated short
convolution, Jamba's Mamba layers (the one with a bias).

One function, :func:`_causal_conv`, over a decode step or a two-segment
batch, in two forms chosen by what the batch's layout says of a row (the
first ``len(batch["state_slot"])`` rows of the flat buffer are one-token
rows, the rest is the tile segment, ``prefill_tile`` rows a tile):

* **one-token rows** are their own chunks: a row's ``K - 1`` earlier inputs
  are its slot's tail, so the segment is elementwise but for reading and
  writing ``S`` slots of the pool in another order.  Both are one-hot
  matmuls (exact: one term a row, float32 accumulation); a gather and a
  scatter of ``S`` rows are ``S`` serial updates on the chip (22.5 ms of a
  256-row Jamba2 decode tick, 4.2 ms of a 128-row Olmo-Hybrid one, through
  the chunk form).
* **the tile segment** holds few chunks, one a tile at the most: the chunk
  form (in-chunk taps by shifted rows, the tail's part scatter-added into a
  chunk's first ``K - 1`` rows, the new tail gathered out of the rows and
  the old tail) on a batch of one entry a TILE, so its gathers and scatters
  are ``T / prefill_tile`` long and not ``S``.

**The pool is flat**: ``[slots + 1, (K - 1) C]``, a slot's ``K - 1`` rows of
``C`` back to back in ONE row (tap ``j`` is the lane slice ``[j C, (j + 1)
C)``; the oldest input first).  A ``[slots + 1, K - 1, C]`` pool is a tile
of two or three sublanes a slot, which XLA re-tiles whole around every
read.  The last slot is the pool's scratch slot; this function never
touches it (a pad row names no slot: it reads zeros and writes nothing),
so nothing a pad row computes can reach a live row through the products,
where a non-finite value in ANY slot's row would reach every row (``0 x
inf``): the tails are a layer's own inputs, finite wherever the model is.

**What the one-hot products cost**: ``4 S (slots + 1) (K - 1) C``
operations (the read and the write) and one stream of the pool a call,
whatever the batch holds.  Olmo-Hybrid's 128 rows on 129 slots of 3 x
11,520 lanes: 2.3 GFLOP, ~12 us at the chip's bf16 peak beside the pool's
8.9 MB, ~11 us of HBM; Jamba2's 257 x 15,360 at 256 rows (the largest the
benchmark has): 4.0 GFLOP, ~21 us beside ~10 us.  The products grow with
``S x slots``, the stream with ``slots``: at the engines' ``S = slots``
they pass the stream near 120 slots, and at 512 slots of 34,560 lanes they
are ~185 us a layer, what streaming such a layer's own weights costs.  So
the form is free up to a few hundred slots (a float32 pool, six passes at
``Precision.HIGHEST``: up to about two hundred); past that the tails want
a kernel that moves the hit rows alone."""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _chunk_conv(u32, w32, tails, batch):
    """The chunk form: the taps over each chunk of ``batch`` (contiguous
    rows ``chunk_start .. logits_idx`` of ``u32`` [T, C], float32) with the
    chunk's entry of ``tails`` [entries + 1, K - 1, C] before its first row
    (zeros when the chunk starts at position 0; the last entry is the
    scratch one: a ``state_slot`` that names it has no chunk).  Returns
    ``(float32 sums [T, C], new tails)``; a gather or scatter per entry."""
    scratch = tails.shape[0] - 1
    t_rows, taps = u32.shape[0], w32.shape[0]
    start, sslot = batch["chunk_start"], batch["state_slot"]
    n = batch["logits_idx"] - start + 1               # [S] chunk lengths
    live = sslot != scratch
    i_row = jnp.arange(t_rows, dtype=jnp.int32) - start[batch["token_slot"]]
    acc = u32 * w32[taps - 1]
    for back in range(1, taps):
        prev = jnp.pad(u32, ((back, 0), (0, 0)))[:t_rows]
        acc += jnp.where((i_row >= back)[:, None], prev, 0.0) \
            * w32[taps - 1 - back]
    fresh = batch["token_pos"][start] == 0            # chunk starts at 0
    tail = tails[sslot].astype(F32) \
        * jnp.where(fresh, 0.0, 1.0)[:, None, None]   # [S, K-1, C]
    # what the tail gives a chunk's row i < K - 1: taps reaching before it
    ar = jnp.arange(taps - 1, dtype=jnp.int32)
    contrib = jnp.stack([
        sum(w32[taps - 1 - back] * tail[:, taps - 1 + i - back]
            for back in range(i + 1, taps)) for i in range(taps - 1)], 1)
    rows = jnp.where(live[:, None] & (ar[None, :] < n[:, None]),
                     start[:, None] + ar[None, :], t_rows)
    acc = acc.at[rows].add(contrib, mode="drop")
    # the sequence's last K - 1 inputs after this chunk
    idx = n[:, None] - (taps - 1) + ar[None, :]       # in-chunk, may be < 0
    from_u = u32[jnp.clip(start[:, None] + idx, 0, t_rows - 1)]
    from_tail = jnp.take_along_axis(
        tail, jnp.clip(idx + taps - 1, 0, taps - 2)[:, :, None], axis=1)
    new_tail = jnp.where((idx >= 0)[:, :, None], from_u, from_tail)
    return acc, tails.at[sslot].set(new_tail.astype(tails.dtype))


def _causal_conv(u, w, conv_pool, batch, activation=_silu, bias=None,
                 prefill_tile=None):
    """Depthwise causal convolution over each chunk of a decode step or a
    two-segment batch, plus ``bias`` [C] where the layer has one (a Mamba
    layer's), then ``activation`` (SiLU for the Gated DeltaNet and Mamba
    layers; None: the convolution as it is, LFM2's gated short
    convolution).  ``u`` [T, C]: this batch's inputs; ``w`` [K, C], the
    last tap on the current token; ``conv_pool`` [slots + 1, (K - 1) C]:
    each sequence's last K - 1 inputs, flat (module doc); ``prefill_tile``:
    rows a tile of the tile segment (a batch that has one must say).  A
    row's earlier inputs are the rows before it in its own chunk and, for a
    chunk's first K - 1 rows, the slot's tail (zeros when the chunk starts
    at position 0, whatever the slot held).  Returns ``(activation(conv)
    [T, C], new conv_pool)``; the sums are float32, the pool keeps its
    dtype."""
    taps, ch = w.shape
    pos, sslot = batch["token_pos"], batch["state_slot"]
    t_rows, s_rows, slots = u.shape[0], sslot.shape[0], conv_pool.shape[0]
    if t_rows > s_rows and not prefill_tile:
        raise ValueError(
            f"_causal_conv: {t_rows - s_rows} rows behind the {s_rows} "
            f"one-token rows and no prefill_tile to cut them into tiles")
    exact = jax.lax.Precision.HIGHEST if conv_pool.dtype == F32 else None
    w32 = w.astype(F32)

    def read(hot):          # [R, slots] one-hot -> the slots' tails
        return jnp.dot(hot.astype(conv_pool.dtype), conv_pool,
                       precision=exact, preferred_element_type=F32)

    def write(hot, tails):  # what the slots named by ``hot`` now hold
        return jnp.dot(hot.T.astype(conv_pool.dtype),
                       tails.astype(conv_pool.dtype), precision=exact,
                       preferred_element_type=F32)

    def finish(acc):
        if bias is not None:
            acc = acc + bias.astype(F32)
        if activation is not None:
            acc = activation(acc)
        return acc.astype(u.dtype)

    lanes = jnp.arange(slots, dtype=jnp.int32)[None, :]
    rows = slice(0, s_rows)
    # a pad row (position -1 in a two-segment batch, the scratch slot's in
    # a decode step) names no slot: an empty one-hot row
    row_slot = sslot[batch["token_slot"][rows]]
    row_slot = jnp.where((pos[rows] >= 0) & (row_slot != slots - 1),
                         row_slot, -1)
    hot = row_slot[:, None] == lanes
    # (the tails stay rows of (K - 1) C lanes: a tap is a lane slice)
    tail = read(hot) * jnp.where(pos[rows] == 0, 0.0, 1.0)[:, None]
    u32 = u[rows].astype(F32)
    x = finish(u32 * w32[taps - 1] + sum(
        w32[j] * tail[:, j * ch:(j + 1) * ch] for j in range(taps - 1)))
    hit = jnp.any(hot, axis=0)
    new = write(hot, jnp.concatenate([tail[:, ch:], u32], axis=1))
    if t_rows > s_rows:
        tile = int(prefill_tile)
        nt = (t_rows - s_rows) // tile
        first = s_rows + jnp.arange(nt, dtype=jnp.int32) * tile
        start = batch["chunk_start"]
        slot_b = batch["token_slot"][first]     # each tile's batch slot
        real = pos[first] >= 0
        # tiles before this one in its chunk; a chunk's first tile is its
        # entry, every other tile's entry is empty (no slot, n = 0)
        back = jnp.where(real, (first - start[slot_b]) // tile, 0)
        head = real & (back == 0)
        ar = jnp.arange(nt, dtype=jnp.int32)
        n = jnp.where(head, batch["logits_idx"][slot_b]
                      - start[slot_b] + 1, 0)
        hot = jnp.where(head, sslot[slot_b], -1)[:, None] == lanes
        local = jnp.concatenate([
            read(hot).astype(conv_pool.dtype).reshape(nt, taps - 1, ch),
            jnp.zeros((1, taps - 1, ch), conv_pool.dtype)])
        acc, local = _chunk_conv(u[s_rows:].astype(F32), w32, local, {
            "chunk_start": ar * tile,
            "state_slot": jnp.where(head, ar, nt),
            "logits_idx": ar * tile + n - 1,
            "token_slot": jnp.repeat(ar - back, tile),
            "token_pos": pos[s_rows:]})
        x = jnp.concatenate([x, finish(acc)])
        hit = hit | jnp.any(hot, axis=0)
        new = new + write(hot, local[:nt].reshape(nt, -1))
    return x, jnp.where(hit[:, None], new.astype(conv_pool.dtype),
                        conv_pool)
