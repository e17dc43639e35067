"""``modules/conv.py::_causal_conv`` against a plain convolution of each
sequence's whole history (NumPy, float64, zeros before position 0) that knows
nothing of slots, tiles or segments.

A scenario is a list of steps, a step a list of ``(sequence, tokens)`` in
batch order: packed here the way the engine packs (``_device_decode_batch``
for a decode step, ``RaggedBatchWrapper.finalize`` for a two-segment batch),
run through the function with the pool the step before returned.  Every
slot starts as garbage, the scratch slot included, pad rows carry random
inputs, and after every step each slot no sequence of the batch names is
bitwise what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.modules import conv

S, TILE, SLOTS, CH = 4, 8, 5, 8


def _decode_batch(entries, seen, slot_of):
    """``engine_v2._device_decode_batch``: a row a batch slot, pad rows on
    the scratch slot at a position that is NOT negative."""
    pos = np.full((S,), 7, np.int32)
    sslot = np.full((S,), SLOTS, np.int32)
    for i, (seq, n) in enumerate(entries):
        assert n == 1
        pos[i], sslot[i] = seen[seq], slot_of[seq]
    ar = np.arange(S, dtype=np.int32)
    return {"token_slot": ar, "token_pos": pos, "logits_idx": ar,
            "chunk_start": ar, "state_slot": sslot}, list(ar[:len(entries)])


def _two_segment_batch(entries, seen, slot_of, tiles):
    """``RaggedBatchWrapper.finalize`` after ``set_alignment(TILE)``."""
    t_rows = S + tiles * TILE
    token_slot = np.zeros((t_rows,), np.int32)
    token_pos = np.full((t_rows,), -1, np.int32)
    start = np.zeros((S,), np.int32)
    sslot = np.full((S,), SLOTS, np.int32)
    logits_idx = np.zeros((S,), np.int32)
    singles, used, starts = 0, 0, []
    for i, (seq, n) in enumerate(entries):
        if n == 1:
            cursor, singles = singles, singles + 1
        else:
            cursor = S + -(-used // TILE) * TILE
            used = cursor - S + n
        assert cursor + n <= t_rows
        token_slot[cursor:cursor + n] = i
        token_pos[cursor:cursor + n] = seen[seq] + np.arange(n)
        start[i], sslot[i], logits_idx[i] = cursor, slot_of[seq], \
            cursor + n - 1
        starts.append(cursor)
    return {"token_slot": token_slot, "token_pos": token_pos,
            "logits_idx": logits_idx, "chunk_start": start,
            "state_slot": sslot}, starts


# (history each sequence starts with, steps); a step: (entries, tiles), tiles
# None = a decode step; sequence ``q`` sits in slot ``SLOT_OF[q]``
SLOT_OF = {"a": 2, "b": 0, "c": 3, "d": 4}
SCENARIOS = {
    # every row a decode row of a sequence well past the taps, one pad row
    "decode_only": ({"a": 9, "b": 4, "c": 1}, [
        ([("a", 1), ("b", 1), ("c", 1)], None)]),
    # the layout ``test_ragged_lfm2.py`` held: [dec, pad, pad, pad | a
    # fresh chunk of 5, 3 pad | a chunk of 2 continuing at position 4]
    "two_segments": ({"a": 9, "c": 4}, [
        ([("a", 1), ("b", 5), ("c", 2)], 2)]),
    # a one-token prompt: a row at position 0 in a slot that holds garbage
    "row_at_position_0": ({"a": 6}, [
        ([("b", 1), ("a", 1)], None), ([("b", 1), ("a", 1)], 1)]),
    # a chunk shorter than K - 1 at taps 4, twice, then decoded
    "chunk_shorter_than_the_taps": ({"a": 1, "b": 5}, [
        ([("a", 2), ("b", 1)], 1), ([("a", 2), ("b", 2)], 2),
        ([("a", 1), ("b", 1)], None)]),
    # a chunk over two tiles beside one that starts in the third, a padded
    # fourth tile, two decode rows before them
    "chunk_over_two_tiles": ({"a": 3, "c": 2, "d": 11}, [
        ([("c", 1), ("a", 13), ("d", 1), ("b", 6)], 4)]),
    # prompt, decode, decode, the prompt's second chunk beside decodes:
    # each step reads the pool the step before returned
    "steps_in_a_row": ({}, [
        ([("a", 9), ("b", 3)], 4), ([("a", 1), ("b", 1)], None),
        ([("b", 1), ("a", 1), ("c", 8)], 1), ([("c", 5), ("a", 1)], 1),
        ([("a", 1), ("b", 1), ("c", 1)], None)]),
}
# taps, activation, bias, pool dtype
FORMS = {
    "k3_plain_f32": (3, None, False, jnp.float32),
    "k3_silu_f32": (3, "silu", False, jnp.float32),
    "k3_bias_f32": (3, None, True, jnp.float32),
    "k3_silu_bias_f32": (3, "silu", True, jnp.float32),
    "k4_silu_bf16": (4, "silu", False, jnp.bfloat16),
    "k4_silu_bias_bf16": (4, "silu", True, jnp.bfloat16),
    "k4_plain_f32": (4, None, False, jnp.float32),
}


def _plain(history, w, bias, activation, n):
    """The last ``n`` outputs of the whole history's convolution."""
    taps = len(w)
    padded = np.concatenate([np.zeros((taps - 1, CH)), history])
    out = sum(w[j] * padded[j:j + len(history)] for j in range(taps))
    if bias is not None:
        out = out + bias
    if activation:
        out = out / (1.0 + np.exp(-out))
    return out[-n:]


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_causal_conv_matches_a_plain_convolution(scenario, form):
    taps, activation, has_bias, dtype = FORMS[form]
    histories, steps = SCENARIOS[scenario]
    rng = np.random.default_rng(57)

    def draw(*shape):   # values the pool's dtype holds exactly
        return np.asarray(jnp.asarray(rng.standard_normal(shape), dtype),
                          np.float64)

    w = rng.standard_normal((taps, CH))
    bias = rng.standard_normal((CH,)) if has_bias else None
    history = {q: draw(histories.get(q, 0), CH) for q in SLOT_OF}
    pool = draw(SLOTS + 1, (taps - 1) * CH)           # garbage everywhere
    for q, h in history.items():
        if len(h):
            tail = np.concatenate([np.zeros((taps - 1, CH)), h])[-(taps - 1):]
            pool[SLOT_OF[q]] = tail.reshape(-1)
    pool = jnp.asarray(pool, dtype)
    kw = {"activation": conv._silu if activation else None,
          "bias": None if bias is None else jnp.asarray(bias, jnp.float32)}
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for entries, tiles in steps:
        seen = {q: len(h) for q, h in history.items()}
        if tiles is None:
            batch, starts = _decode_batch(entries, seen, SLOT_OF)
            t_rows = S
        else:
            batch, starts = _two_segment_batch(entries, seen, SLOT_OF, tiles)
            t_rows = S + tiles * TILE
        u = draw(t_rows, CH)                          # pad rows too
        got, new_pool = conv._causal_conv(
            jnp.asarray(u, dtype), jnp.asarray(w, jnp.float32), pool,
            {k: jnp.asarray(v) for k, v in batch.items()},
            prefill_tile=TILE if tiles else None, **kw)
        assert got.dtype == dtype and new_pool.dtype == dtype \
            and new_pool.shape == pool.shape
        got = np.asarray(got, np.float64)
        named = set()
        for (q, n), at in zip(entries, starts):
            history[q] = np.concatenate([history[q], u[at:at + n]])
            want = _plain(history[q], w, bias, activation, n)
            assert np.allclose(got[at:at + n], want, atol=tol, rtol=tol), \
                (q, n, at)
            # the slot holds the sequence's last K - 1 inputs to the bit:
            # zeros before position 0, whatever it held
            tail = np.concatenate(
                [np.zeros((taps - 1, CH)), history[q]])[-(taps - 1):]
            assert np.array_equal(
                np.asarray(new_pool[SLOT_OF[q]], np.float64),
                tail.reshape(-1)), (q, n, at)
            named.add(SLOT_OF[q])
        # pad rows and padded tiles change no slot, the scratch one included
        for slot in set(range(SLOTS + 1)) - named:
            assert np.array_equal(np.asarray(new_pool[slot], np.float64),
                                  np.asarray(pool[slot], np.float64)), slot
        pool = new_pool


def test_causal_conv_without_a_bias_traces_the_program_it_traced():
    """``bias=None`` (LFM2's and the Gated DeltaNet calls) adds nothing to
    the program: the jaxpr is the one of a call that never names the
    argument, and a bias is one more ``add`` over each segment's rows."""
    f = lambda *s: jnp.zeros(s, jnp.float32)
    batch = {"chunk_start": jnp.zeros((2,), jnp.int32),
             "state_slot": jnp.zeros((2,), jnp.int32),
             "logits_idx": jnp.zeros((2,), jnp.int32),
             "token_slot": jnp.zeros((18,), jnp.int32),
             "token_pos": jnp.zeros((18,), jnp.int32)}
    args = (f(18, 8), f(3, 8), f(3, 16), batch)
    plain = str(jax.make_jaxpr(
        lambda *a: conv._causal_conv(*a, prefill_tile=8))(*args))
    named = str(jax.make_jaxpr(lambda *a: conv._causal_conv(
        *a, bias=None, prefill_tile=8))(*args))
    biased = str(jax.make_jaxpr(lambda *a: conv._causal_conv(
        *a, bias=f(8), prefill_tile=8))(*args))
    assert plain == named
    assert biased.count(" add ") == plain.count(" add ") + 2


def test_a_tile_segment_without_its_tile_is_refused():
    f = lambda *s: jnp.zeros(s, jnp.float32)
    batch = {k: jnp.zeros((2,), jnp.int32)
             for k in ("chunk_start", "state_slot", "logits_idx")}
    batch.update(token_slot=jnp.zeros((18,), jnp.int32),
                 token_pos=jnp.zeros((18,), jnp.int32))
    with pytest.raises(ValueError, match="prefill_tile"):
        conv._causal_conv(f(18, 8), f(3, 8), f(3, 16), batch)
