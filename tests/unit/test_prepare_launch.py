"""PR 40: ``put`` in two halves.  ``prepare`` builds a ragged batch and stops
short of the device (rows whose token does not exist yet hold a
placeholder); ``launch`` patches those tokens in, uploads, dispatches and
does what follows a dispatch; ``discard`` drops a batch that will not be
launched.  The bar: the two halves return what ``put`` returns on the same
inputs, and a discarded preparation leaves the engine as it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

CFG = LlamaConfig.tiny(dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _engine(params, prefix=False, tile=None):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 32,
                          "max_ragged_sequence_count": 4,
                          "max_context": 96},
        "kv_cache": {"block_size": 8, "enable_prefix_cache": prefix}})
    eng = InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)
    if tile:
        eng.PREFILL_TILE = tile
    return eng


def _ids(n, seed):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=(n,)).tolist()


def _rows(prepared, logits, nxt):
    """{uid: (logits row, token)} of the slots the batch drained."""
    logits, nxt = np.asarray(logits, np.float32), np.asarray(nxt)
    return {uid: (logits[slot], int(nxt[slot]))
            for slot, (uid, last) in enumerate(zip(prepared.scheduled,
                                                   prepared.drained))
            if last}


@pytest.mark.parametrize("tile", [None, 16], ids=["packed", "two_segment"])
@pytest.mark.parametrize("late", [False, True],
                         ids=["host_tokens", "late_rows_patched"])
def test_prepare_then_launch_returns_what_put_returns(params, late, tile):
    """A decoding row, a prompt's second chunk and a new prompt in one
    batch: through ``put`` on one engine, through ``prepare`` + ``launch``
    on another, the decoding row's token known from the start or patched
    in at the launch."""
    a, b, c = _ids(9, 1), _ids(34, 2), _ids(7, 3)
    one, two = _engine(params, tile=tile), _engine(params, tile=tile)
    for eng in (one, two):
        tok = eng.put([1, 2], [a, b[:20]], greedy=True)[1]
    want_logits = one.put([1, 2, 3], [[tok], b[20:], c])
    assert sorted(want_logits) == [1, 2, 3]

    before = two.last_launch
    prepared = two.prepare([1, 2, 3], [[0 if late else tok], b[20:], c],
                           late=[1] if late else ())
    sm = two.state_manager
    # nothing has moved yet: no launch, no position, the tokens wait
    assert two.last_launch == before
    assert [sm.get_sequence(u).seen_tokens for u in (1, 2, 3)] == [9, 20, 0]
    assert [len(sm.get_sequence(u).pending) for u in (1, 2, 3)] == [1, 14, 7]
    assert prepared.scheduled == [1, 2, 3] and all(prepared.drained)
    assert sorted(prepared.late) == ([1] if late else [])
    logits, nxt, launch = two.launch(prepared, {1: tok} if late else None)
    assert launch == two.last_launch == before + 1
    got = _rows(prepared, logits, nxt)
    for uid, row in want_logits.items():
        np.testing.assert_array_equal(got[uid][0], row)
        assert got[uid][1] == int(np.argmax(row))
    for uid in (1, 2, 3):
        s1, s2 = (e.state_manager.get_sequence(uid) for e in (one, two))
        assert (s1.seen_tokens, s1.pending, s1.blocks) == \
            (s2.seen_tokens, s2.pending, s2.blocks)
    assert one.step_keys == two.step_keys       # the same programs


def test_a_chunk_past_the_budget_stays_pending_as_under_put(params):
    """``prepare`` builds ONE batch: what SplitFuse leaves out of it waits
    in the queue for the next, as between two forwards of one ``put``."""
    eng, ref = _engine(params), _engine(params)
    ids = _ids(50, 4)
    prepared = eng.prepare([5], [ids])
    assert prepared.chunk_sizes == [32] and prepared.drained == [False]
    eng.launch(prepared)
    seq = eng.state_manager.get_sequence(5)
    assert (seq.seen_tokens, len(seq.pending)) == (32, 18)
    second = eng.prepare([5])               # from what is pending
    assert second.chunk_sizes == [18] and second.drained == [True]
    logits, nxt, _ = eng.launch(second)
    np.testing.assert_array_equal(
        np.asarray(logits, np.float32)[0], ref.put([5], [ids])[5])
    assert eng.prepare([5]) is None         # nothing is pending


def test_a_discarded_preparation_leaves_the_engine_as_it_was(params):
    """Sequence 1 decodes, 2 is mid-prompt, 3 is new and shares two cached
    blocks with 1: a batch of the three is prepared (1 on a late token) and
    discarded.  Positions, queues and the prefix cache's counters are what
    they were; the allocator is short of exactly the blocks the two
    sequences that were there took for their chunks, which they keep; the
    new sequence is gone.  The same batch through ``put`` then gives what
    it gives on an engine that never prepared it."""
    a, b = _ids(23, 5), _ids(40, 6)
    c = a[:16] + _ids(9, 7)
    eng, ref = _engine(params, prefix=True), _engine(params, prefix=True)
    for e in (eng, ref):
        tok = e.put([1, 2], [a, b[:9]], greedy=True)[1]
    sm = eng.state_manager
    stats = eng.prefix_cache_stats

    def state():
        return ({u: (s.seen_tokens, list(s.pending), s.shared_blocks)
                 for u, s in sm._seqs.items()}, stats.as_dict())

    was, free = state(), sm.free_blocks
    held = {u: len(sm.get_sequence(u).blocks) for u in (1, 2)}
    prepared = eng.prepare([1, 2, 3], [[0], b[9:], c], late=[1])
    assert sm.get_sequence(3) is not None and stats.hit_tokens == 16
    assert sm.get_sequence(1).pending == [0]        # the placeholder
    eng.discard(prepared)
    assert state() == was
    assert sm.get_sequence(3) is None
    # 23 -> 24 tokens fit sequence 1's third block; 9 -> 40 take three more
    kept = {u: len(sm.get_sequence(u).blocks) - held[u] for u in (1, 2)}
    assert kept == {1: 0, 2: 3}
    assert sm.free_blocks == free - sum(kept.values())
    assert eng.last_launch == ref.last_launch       # nothing was dispatched

    got = eng.put([1, 2, 3], [[tok], b[9:], c])
    want = ref.put([1, 2, 3], [[tok], b[9:], c])
    for uid in (1, 2, 3):
        np.testing.assert_array_equal(got[uid], want[uid])
    assert eng.prefix_cache_stats.as_dict() == ref.prefix_cache_stats.as_dict()
    eng.flush([1, 2, 3])
    assert sm.n_tracked_sequences == 0 and \
        sm.free_blocks == sm.allocator.num_blocks - 1


def test_late_rows_are_checked(params):
    eng = _engine(params)
    eng.put([1], [_ids(9, 8)])
    with pytest.raises(ValueError, match="only a one-token row"):
        eng.prepare([2], [_ids(5, 9)], late=[2])
    eng.flush([2])
    prepared = eng.prepare([1], [[0]], late=[1])
    with pytest.raises(ValueError, match="tokens came for"):
        eng.launch(prepared)                # the late row's token is owed
    with pytest.raises(ValueError, match="tokens came for"):
        eng.launch(prepared, {1: 3, 2: 4})
    eng.discard(prepared)
    seq = eng.state_manager.get_sequence(1)
    assert (seq.seen_tokens, seq.pending) == (9, [])
