"""The depthwise causal convolution of the families that keep a
convolution's tail per sequence in a state slot (``ragged/state_pool.py``):
Qwen3-Next's Gated DeltaNet layers, LFM2's gated short convolution, Jamba's
Mamba layers (the one with a bias)."""

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _causal_conv(u, w, conv_pool, batch, activation=_silu, bias=None):
    """Depthwise causal convolution over each chunk of a ragged batch, plus
    ``bias`` [C] where the layer has one (a Mamba layer's), then
    ``activation`` (SiLU for the Gated DeltaNet and Mamba layers; None: the
    convolution as it is, LFM2's gated short convolution).  ``u`` [T, C]:
    this batch's inputs; ``w`` [K, C], the last tap on the current token;
    ``conv_pool`` [slots + 1, K - 1, C]: each sequence's last K - 1 inputs,
    its last slot the scratch one that pad rows write.  A row's earlier
    inputs are the rows before it in its own chunk (chunks are contiguous
    rows) and, for a chunk's first K - 1 rows, the slot's tail (zeros when
    the chunk starts at position 0).  Returns ``(activation(conv) [T, C],
    new conv_pool)``."""
    scratch = conv_pool.shape[0] - 1
    t_rows, taps = u.shape[0], w.shape[0]
    start, sslot = batch["chunk_start"], batch["state_slot"]
    n = batch["logits_idx"] - start + 1               # [S] chunk lengths
    live = sslot != scratch
    i_row = jnp.arange(t_rows, dtype=jnp.int32) - start[batch["token_slot"]]
    w32, u32 = w.astype(F32), u.astype(F32)
    acc = u32 * w32[taps - 1]
    for back in range(1, taps):
        prev = jnp.pad(u32, ((back, 0), (0, 0)))[:t_rows]
        acc += jnp.where((i_row >= back)[:, None], prev, 0.0) \
            * w32[taps - 1 - back]
    fresh = batch["token_pos"][start] == 0            # chunk starts at 0
    tail = conv_pool[sslot].astype(F32) \
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
    if bias is not None:
        acc = acc + bias.astype(F32)
    if activation is not None:
        acc = activation(acc)
    return acc.astype(u.dtype), conv_pool.at[sslot].set(
        new_tail.astype(conv_pool.dtype))
