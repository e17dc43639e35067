"""Two kinds of KV layer in one cache manager (a model's ``kv_groups``):
the window group's pool, allocator and per-sequence table beside the global
group's, on the host alone where no model is needed and through a tiny
``RaggedAfmoe`` engine where one is.

What is checked: the window pool's size comes from the window, the budget
and the sequence count and not from ``max_context``; a sequence's window
table never holds more than its bound and the pool, sized under that bound
times the sequences, never overflows with every slot at ``max_context`` or
under any split of the budget; a flush and a preemption by recompute leave
both allocators full; the metadata of a batch names each group's table and
write target; the counters on ``engine/build_batch`` and
``engine/decode_prep`` equal a hand count; every path that knows one table
refuses by name.  (That a model without ``kv_groups`` lowers to the text it
lowered to before there were groups is checked against the parent commit by
``benchmark/tools/calls/pr39_jaxprs.py``.)
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)

from benchmark.families import afmoe as family                # noqa: E402
from deepspeed_tpu.inference.v2 import (                      # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.config_v2 import (            # noqa: E402
    DSStateManagerConfig, KVCacheConfig)
from deepspeed_tpu.inference.v2.ragged import (               # noqa: E402
    BlockedKVCache, DSStateManager, CacheLayoutError, RaggedBatchWrapper)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (  # noqa: E402
    RaggedMetadataError, pack_metadata, packed_length, unpack_metadata)
from deepspeed_tpu.observability.tracer import Tracer         # noqa: E402
from deepspeed_tpu.serving import (                           # noqa: E402
    ContinuousBatchScheduler, SamplingParams, SpeculativeConfig)

BS, WINDOW = 16, 40
GROUPS = {"window": {"layers": [0, 1, 3, 4], "window": WINDOW}}

HF = {"model_type": "afmoe", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 128, "moe_intermediate_size": 32,
      "num_hidden_layers": 5,
      "layer_types": ["sliding_attention", "sliding_attention",
                      "full_attention", "sliding_attention",
                      "sliding_attention"],
      "global_attn_every_n_layers": 4, "sliding_window": WINDOW,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "num_dense_layers": 1, "num_experts": 4, "router_experts": 8,
      "expert_start": 0, "num_experts_per_tok": 2, "num_shared_experts": 1,
      "n_group": 1, "topk_group": 1, "score_func": "sigmoid",
      "route_norm": True, "route_scale": 2.448, "mup_enabled": True,
      "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 4096, "tie_word_embeddings": False}


def _manager(max_context=256, seqs=4, budget=32, blocks=64, **kv):
    return DSStateManager(
        DSStateManagerConfig(max_ragged_batch_size=budget,
                             max_ragged_sequence_count=seqs,
                             max_context=max_context),
        KVCacheConfig(block_size=BS, num_blocks=blocks, **kv),
        num_layers=5, num_kv_heads=2, head_dim=16, dtype=jnp.float32,
        kv_groups=GROUPS)


def params(hf=HF, seed=0, dtype=jnp.float32):
    shapes = family.serve_param_shapes(hf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.key(seed)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        std = family.init_std([str(getattr(p, "key", p)) for p in path],
                              leaf.shape)
        leaves.append(jnp.ones(leaf.shape, dtype) if std is None else (
            jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                              jnp.float32) * std).astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def engine(p, hf=HF, compute=jnp.float32, budget=32, tile=None, blocks=64,
           max_context=256, seqs=4, **kv):
    model = family.serve_model(hf, BS)
    model._model.config = dataclasses.replace(model._model.config,
                                              dtype=compute)
    eng = InferenceEngineV2(model, p, RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": budget,
                          "max_ragged_sequence_count": seqs,
                          "max_context": max_context},
        "kv_cache": {"block_size": BS, "num_blocks": blocks, **kv}}))
    if tile:
        eng.PREFILL_TILE = tile
    return eng


def ids(n, seed=3, vocab=256):
    return np.random.default_rng(seed).integers(0, vocab, size=(n,))


# ------------------------------------------------------------------ #
# (a) the pools
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("max_context", [256, 4096])
def test_window_pool_is_sized_from_window_budget_and_slots(max_context):
    sm = _manager(max_context=max_context)
    # a table's own bound: ceil((40 + 32) / 16) + 1 = 6 blocks; the pool:
    # 39 = 2 x 16 + 7, so 4 sequences x (2 + 2) + (4 x 7 + 32) // 16 = 19,
    # under 4 x 6, + trash
    assert sm.window_table_bound == 6
    assert sm.window_pool_blocks == 4 * 4 + 3
    assert sm.win_allocator.num_blocks == 19 + 1
    kv = sm.kv_cache
    assert kv.num_blocks == 64 and kv.window_blocks == 20
    for i in range(5):
        rows = kv.cache[f"layer_{i}"]["k"].shape[0]
        assert rows == (64 if i == 2 else 20) * BS
    # the gauges' bytes: the global layer a token, the window pools whole
    assert kv.per_token_bytes == 1 * 2 * 2 * 16 * 4
    assert kv.window_pool_bytes == 4 * 20 * BS * 2 * 2 * 16 * 4
    assert sm.allocator.num_blocks == 64 and sm.free_blocks == 63


def test_block_operations_over_every_layer_refuse():
    kv = BlockedKVCache(5, 8, BS, 2, 16, jnp.float32,
                        window_layers=(0, 1, 3, 4), window_blocks=4)
    for call in (lambda: kv.copy_block(1, 2), lambda: kv.gather_blocks([1]),
                 lambda: kv.scatter_blocks([1], {})):
        with pytest.raises(CacheLayoutError, match="two pools"):
            call()


def test_release_follows_the_band_and_flush_returns_both_groups():
    sm = _manager()
    seq = sm.get_or_create_sequence(1)
    held = []
    for step in range(0, 250, 25):          # chunks of 25 tokens
        was = seq.win_first
        assert sm.release_window(seq) == seq.win_first - was
        sm.maybe_allocate_kv(seq, 25)
        # the live entries reach from the first key a query at ``step``
        # sees to the chunk's last position
        assert seq.win_first == max(0, step - WINDOW + 1) // BS
        assert (seq.win_first + len(seq.win_blocks)) * BS >= step + 25
        assert len(seq.win_blocks) <= sm.window_table_bound
        held.append(len(seq.win_blocks))
        seq.seen_tokens += 25
    assert len(seq.blocks) == -(-250 // BS)         # the global group: all
    assert max(held) == 5 and sm.win_released == seq.win_first > 0
    sm.flush_sequence(1)
    assert sm.allocator.free_blocks == 63
    assert sm.win_allocator.free_blocks == 19


def test_pool_never_overflows_with_every_slot_at_max_context():
    """Four sequences fed to ``max_context`` in budget-sized chunks, one
    after the other and then token by token together, as the engine feeds
    them (every sequence's release, then the forward's allocations): the
    window allocator never runs out and no table passes its bound."""
    sm = _manager(max_context=512, blocks=4 * 32 + 1)
    seqs = [sm.get_or_create_sequence(u) for u in range(4)]
    for seq in seqs:
        while seq.seen_tokens < 480:
            sm.release_windows()
            sm.maybe_allocate_kv(seq, 32)
            assert len(seq.win_blocks) <= sm.window_table_bound
            seq.seen_tokens += 32
    while seqs[0].seen_tokens < 512:
        sm.release_windows()
        for seq in seqs:
            sm.maybe_allocate_kv(seq, 1)
            seq.seen_tokens += 1
    assert all(len(s.win_blocks) <= 4 for s in seqs)   # 39 // 16 + 2
    assert all(len(s.blocks) == 32 for s in seqs)
    sm.flush(range(4))
    assert sm.win_allocator.free_blocks == sm.window_pool_blocks == 19


@pytest.mark.parametrize("window,budget,seed", [
    (40, 32, 0), (40, 32, 1), (33, 32, 2), (48, 64, 3), (17, 16, 4),
    (64, 48, 5)])
def test_any_split_of_the_budget_fits_the_pool(window, budget, seed):
    """The pool's size is a sum over sequences, not a sequence's worst case
    times their count: forwards that split the budget any way between
    chunks and one-token rows of every slot, with flushes and new arrivals
    in between, never find the window allocator empty, and come within a
    block a sequence of the pool's whole size."""
    rng = np.random.default_rng(seed)
    groups = {"window": {"layers": [0, 1, 3, 4], "window": window}}
    sm = DSStateManager(
        DSStateManagerConfig(max_ragged_batch_size=budget,
                             max_ragged_sequence_count=4, max_context=600),
        KVCacheConfig(block_size=BS, num_blocks=4 * 40 + 1),
        num_layers=5, num_kv_heads=2, head_dim=16, dtype=jnp.float32,
        kv_groups=groups)
    assert sm.window_pool_blocks < 4 * sm.window_table_bound
    live, nxt, peak = {}, 0, 0
    for _ in range(3000):
        while len(live) < 4:                    # an arrival a free slot
            live[nxt] = sm.get_or_create_sequence(nxt)
            nxt += 1
        sm.release_windows()
        left = budget
        for uid in rng.permutation(list(live)):
            seq = live[uid]
            n = int(min(left, 600 - seq.seen_tokens,
                        rng.choice([1, 1, 1, 15, 16, 17, budget])))
            if n <= 0:
                continue
            sm.maybe_allocate_kv(seq, n)        # raises if the pool is out
            assert len(seq.win_blocks) <= sm.window_table_bound
            seq.seen_tokens += n
            left -= n
        peak = max(peak, sm.window_pool_blocks
                   - sm.win_allocator.free_blocks)
        for uid in [u for u, s in live.items()
                    if s.seen_tokens >= 600 or rng.random() < 0.002]:
            sm.flush_sequence(uid)
            del live[uid]
    assert sm.window_pool_blocks - 4 <= peak <= sm.window_pool_blocks


def test_window_blocks_needed_counts_its_own_release():
    sm = _manager()
    assert sm.window_blocks_needed(None, 20) == 2
    # a prompt longer than the budget is fed in chunks: never the bound + 1
    assert sm.window_blocks_needed(None, 250) == sm.window_table_bound
    seq = sm.get_or_create_sequence(1)
    sm.maybe_allocate_kv(seq, 100)
    seq.seen_tokens = 100                   # entries 0-6 held, 3-6 live
    assert sm.window_blocks_needed(seq, 1) == 0
    assert sm.window_blocks_needed(seq, 13) == 1    # position 112: entry 7
    sm.release_window(seq)
    assert (seq.win_first, len(seq.win_blocks)) == (3, 4)
    assert sm.window_blocks_needed(seq, 13) == 1


# ------------------------------------------------------------------ #
# (b) the metadata of a batch
# ------------------------------------------------------------------ #
def test_metadata_names_each_groups_table_and_write_target():
    sm = _manager()
    a, b = sm.get_or_create_sequence(1), sm.get_or_create_sequence(2)
    sm.maybe_allocate_kv(a, 70)
    a.seen_tokens = 70
    sm.release_window(a)                    # entry 0 falls out (31 // 16)
    sm.maybe_allocate_kv(a, 1)
    sm.maybe_allocate_kv(b, 20)
    batch = RaggedBatchWrapper(32, 4, 16, BS, window=WINDOW)
    batch.insert_sequence(a, np.asarray([5]))
    batch.insert_sequence(b, np.arange(20))
    meta = batch.finalize(32)
    tw = meta["block_tables_win"]
    assert tw[0, 0] == 0 and list(tw[0, 1:5]) == a.win_blocks
    assert (tw[0, 5:] == 0).all() and list(tw[1, :2]) == b.win_blocks
    assert list(meta["block_tables"][0, :5]) == a.blocks
    assert meta["kv_dest_win"][0] == a.win_blocks[-1] * BS + 70 % BS
    assert meta["kv_dest"][0] == a.blocks[-1] * BS + 70 % BS
    assert list(meta["kv_dest_win"][1:21]) == [
        b.win_blocks[p // BS] * BS + p % BS for p in range(20)]
    assert (meta["kv_dest_win"][21:] == 0).all()     # pads: the trash block
    packed = pack_metadata(meta)
    assert packed.shape == (packed_length(32, 4, 16, win=True),)
    back = unpack_metadata(packed, 32, 4, 16, win=True)
    assert all((back[k] == meta[k]).all() for k in back)
    assert set(back) == set(meta) - {"n_valid"}


def test_a_window_table_short_of_the_band_is_caught():
    sm = _manager()
    a = sm.get_or_create_sequence(1)
    sm.maybe_allocate_kv(a, 70)
    a.seen_tokens = 70
    sm.release_window(a)
    sm.maybe_allocate_kv(a, 1)
    sm.win_allocator.free(a.win_blocks[:1])     # one block too many
    del a.win_blocks[:1]
    a.win_first += 1
    batch = RaggedBatchWrapper(32, 4, 16, BS, window=WINDOW)
    batch.insert_sequence(a, np.asarray([5]))
    with pytest.raises(RaggedMetadataError, match="window table"):
        batch.finalize(32)


# ------------------------------------------------------------------ #
# (c) through the engine and the scheduler
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


def test_flush_and_preemption_leave_both_allocators_full():
    p = params()
    prompts = [ids(n, seed=10 + i).tolist()
               for i, n in enumerate((90, 40, 70, 33))]
    news = (30, 25, 40, 30)

    def solo(prompt, n):
        sched = ContinuousBatchScheduler(engine(p))
        req = sched.submit(list(prompt), _greedy(n))
        sched.run_until_idle()
        return list(req.generated)

    # 17 usable global blocks of 16 tokens: the four requests together
    # outgrow them while decoding, so the newest is preempted and recomputed
    eng = engine(p, blocks=18)
    sm = eng.state_manager
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(q, _greedy(n)) for q, n in zip(prompts, news)]
    bound = sm.window_table_bound
    while sched.num_pending:
        sched.step()
        assert all(len(s.win_blocks) <= bound for s in sm._seqs.values())
    assert sched.metrics.preemptions >= 1
    assert [list(r.generated) for r in reqs] == [
        solo(q, n) for q, n in zip(prompts, news)]
    assert sm.allocator.free_blocks == 17
    assert sm.win_allocator.free_blocks == sm.win_allocator.num_blocks - 1
    assert sm.win_released > 0


def test_admission_counts_the_window_pool_when_it_binds():
    """More tracked sequences than one forward holds can exhaust the window
    pool first: ``can_allocate`` then says no although the global pool has
    room."""
    eng = engine(params(), blocks=200, seqs=2, max_context=200)
    sm = eng.state_manager
    assert sm.win_allocator.num_blocks - 1 == sm.window_pool_blocks == 10
    eng.put([1], [ids(80).tolist()])
    eng.put([2], [ids(80, seed=4).tolist()])
    held = [len(s.win_blocks) for s in sm._seqs.values()]
    # the second still holds entries 1-4 (entry 0 fell out before its third
    # chunk); the first lost entry 1 too where the second's batch was built
    assert held == [3, 4] and sm.win_allocator.free_blocks == 3
    # admission releases first: as of position 80 entry 1 is out of both
    assert eng.can_allocate([3], [16]) and eng.can_allocate([3], [64])
    assert sm.win_allocator.free_blocks == 4
    assert not eng.can_allocate([3], [65])          # five window blocks
    assert sm.free_blocks > 150


def test_tiled_batches_count_the_chunk_reads_key_steps():
    """A tiled engine's ``engine/build_batch`` says how the tiled read's
    grid fits the chunks: key steps over tiles and layers, the live ones,
    and the four window layers' share of both.  At blocks of 16 the rule's
    step holds the whole table (16 entries) and the whole band ((40 + 16) /
    16 + 1 = 5 entries): one step a tile and layer, every one live."""
    trc = Tracer()
    eng = engine(params(), tile=16)
    sched = ContinuousBatchScheduler(eng, tracer=trc)
    sched.submit(ids(70).tolist(), _greedy(2))
    sched.run_until_idle()
    builds = [r["attrs"] for r in trc.records()
              if r["name"] == "engine/build_batch" and r["attrs"]["tokens"] > 1]
    assert [b["tokens"] for b in builds] == [32, 32, 6]
    assert [b["bucket"] - 4 for b in builds] == [32, 32, 16]
    for b, tiles in zip(builds, (2, 2, 1)):
        assert b["chunk_key_steps"] == b["chunk_live_key_steps"] == 5 * tiles
        assert b["chunk_key_steps_win"] == b["chunk_live_key_steps_win"] \
            == 4 * tiles


def test_counters_match_a_hand_count():
    trc = Tracer()
    eng = engine(params())
    sm = eng.state_manager
    sched = ContinuousBatchScheduler(eng, tracer=trc)
    a = sched.submit(ids(70).tolist(), _greedy(12))
    while len(a.generated) < 3:
        sched.step()
    sched.submit(ids(30, seed=2).tolist(), _greedy(2))
    sched.run_until_idle()
    recs = trc.records()
    builds = [r["attrs"] for r in recs if r["name"] == "engine/build_batch"]
    pool = sm.win_allocator.num_blocks - 1
    assert all(b["win_pool_blocks"] == pool == 19 for b in builds)
    # the prompt's three chunks: 32 + 32 + 6 tokens from 0, 32, 64
    first = builds[:3]
    assert [b["tokens"] for b in first] == [32, 32, 6]
    assert [b["attn_pairs"] for b in first] == [
        sum(t + 1 for t in range(a0, a0 + n))
        for a0, n in ((0, 32), (32, 32), (64, 6))]
    assert [b["attn_pairs_win"] for b in first] == [
        sum(min(t + 1, WINDOW) for t in range(a0, a0 + n))
        for a0, n in ((0, 32), (32, 32), (64, 6))]
    assert [b["win_blocks_held"] for b in first] == [2, 4, 4]
    # before the third chunk (seen 64) entry 0 fell out: (64 - 39) // 16
    assert [b["win_blocks_released"] for b in first] == [0, 0, 1]
    assert all(b["read_blocks"] == b["read_blocks_win"] == 0 for b in first)
    # the mixed batch: sequence a's row at position p beside the 30-token
    # chunk of the other: p // 16 + 1 table blocks, of which those from
    # (p - 39) // 16 are inside the band, in each of 4 window layers
    mixed = [b for b in builds if b["tokens"] == 31]
    assert len(mixed) == 1
    p = 70 + 3
    assert 72 <= p <= 75
    blocks = mixed[0]["read_blocks"]
    assert blocks in (p // BS + 1, (p + 1) // BS + 1)
    assert mixed[0]["read_blocks_win"] == 4 * 3     # entries 2, 3, 4
    assert mixed[0]["attn_pairs"] == 30 * 31 // 2
    preps = [r["attrs"] for r in recs if r["name"] == "engine/decode_prep"]
    assert preps
    for q in preps:
        assert q["win_pool_blocks"] == 19 and q["seqs"] in (1, 2)
        assert q["read_blocks_win"] % 4 == 0
        assert 4 * q["seqs"] <= q["read_blocks_win"] <= 4 * 4 * q["seqs"]
        assert q["read_blocks"] >= q["read_blocks_win"] // 4
    released = sum(r["attrs"]["win_blocks_released"] for r in recs
                   if "win_blocks_released" in (r.get("attrs") or {}))
    assert released == sm.win_released >= 2


@pytest.mark.parametrize("path", [
    "prefix_cache", "host_tier", "verify_step", "decode_loop",
    "flush_to_host_kv", "resume_kv", "speculative"])
def test_paths_that_know_one_table_refuse_by_name(path):
    p = params()
    if path in ("prefix_cache", "host_tier"):
        kv = {"enable_prefix_cache": True, "host_tier": path == "host_tier"}
        with pytest.raises(CacheLayoutError, match="enable_prefix_cache"):
            engine(p, **kv)
        return
    eng = engine(p)
    if path == "speculative":
        with pytest.raises(CacheLayoutError, match="kv_groups"):
            ContinuousBatchScheduler(eng, speculative=SpeculativeConfig())
        return
    eng.put([1], [ids(20).tolist()])
    call = {
        "verify_step": lambda: eng.verify_step([1], [[3, 4]]),
        "decode_loop": lambda: eng.decode_loop([1], [3], 4),
        "flush_to_host_kv": lambda: eng.flush_to_host([1], include_kv=True),
        "resume_kv": lambda: eng.resume(
            9, list(range(8)), kv_state={"seen_tokens": 8, "kv": {}}),
    }[path]
    with pytest.raises(CacheLayoutError, match=path.split("_kv")[0]) as err:
        call()
    assert "RaggedAfmoe" in str(err.value) or "_SeededBias" in str(err.value)
    assert eng.state_manager.get_sequence(1).seen_tokens == 20
    # without the payload both are served: recompute
    eng.flush_to_host([1])
    assert eng.state_manager.win_allocator.free_blocks == 19


def test_generate_takes_the_put_path():
    eng = engine(params())
    out = eng.generate([ids(50).tolist()], max_new_tokens=5)
    assert out[0].shape == (5,)
    assert eng.state_manager.win_allocator.free_blocks == 19


def test_occupancy_gauges_tell_the_groups_apart():
    eng = engine(params())
    eng.put([1], [ids(70).tolist()])
    occ = eng.occupancy()
    assert occ["observability/kv_blocks_live"] == 5
    assert occ["observability/kv_window_blocks_total"] == 19
    # entry 0 fell out before the third chunk (seen 64: 25 // 16)
    assert occ["observability/kv_window_blocks_live"] == 4
    eng.decode_step([1], [3])
    eng.decode_step([1], [4])               # seen 71: entry 1 falls out
    occ = eng.occupancy()
    assert occ["observability/kv_blocks_live"] == 5
    assert occ["observability/kv_window_blocks_live"] == 3
    assert occ["observability/kv_window_pool_bytes"] == \
        eng.state_manager.kv_cache.window_pool_bytes


# ------------------------------------------------------------------ #
# (g) a row a group: the window group's pool at its OWN row width
# ------------------------------------------------------------------ #
ROW_GROUPS = {"window": {"layers": [1, 2, 3], "window": 513,
                         "row": {"ckv": 1152}}}


def _row_manager(seqs=48, budget=1024, blocks=8, groups=ROW_GROUPS,
                 block_size=128, **kw):
    return DSStateManager(
        DSStateManagerConfig(max_ragged_batch_size=budget,
                             max_ragged_sequence_count=seqs,
                             max_context=50176),
        KVCacheConfig(block_size=block_size, num_blocks=blocks),
        num_layers=5, num_kv_heads=1, head_dim=640, dtype=jnp.bfloat16,
        kv_row={"ckv": 640, "idx_k": 128}, kv_groups=groups, **kw)


@pytest.mark.parametrize("window, seqs, budget, want", [
    (513, 48, 1024, 48 * 6 + 8),        # W - 1 = 4 x 128 + 0
    (513, 16, 1024, 16 * 6 + 8),
    (514, 48, 1024, 48 * 6 + (48 + 1024) // 128),   # r = 1
    (512, 48, 1024, 48 * 5 + (48 * 127 + 1024) // 128),
    (4096, 32, 1024, 1095),             # Trinity's, as before
])
def test_window_pool_blocks_by_hand(window, seqs, budget, want):
    sm = _row_manager(seqs=seqs, budget=budget, groups={"window": {
        "layers": [1], "window": window, "row": {"ckv": 1152}}})
    assert sm.window_pool_blocks == want
    assert sm.win_allocator.num_blocks == want + 1


def test_each_group_counts_bytes_at_its_own_row():
    sm = _row_manager()
    kv = sm.kv_cache
    assert kv.cache["layer_1"]["ckv"].shape == (297 * 128, 1152)
    assert kv.cache["layer_0"]["ckv"].shape == (8 * 128, 640)
    assert kv.cache["layer_4"]["idx_k"].shape == (8 * 128, 128)
    assert "idx_k" not in kv.cache["layer_2"]
    assert kv.layer_token_bytes == 768 * 2
    assert kv.window_layer_token_bytes == 1152 * 2
    assert kv.per_token_bytes == 2 * 1536
    assert kv.window_token_bytes == 3 * 2304
    assert kv.window_pool_bytes == 297 * 128 * 6912 == 262_766_592


def test_the_gauges_count_each_group_at_its_own_row():
    from deepspeed_tpu.observability.memory import kv_occupancy

    sm = _row_manager(blocks=16)
    seq = sm.get_or_create_sequence(1)
    sm.maybe_allocate_kv(seq, 1024)
    seq.seen_tokens = 1024
    occ = kv_occupancy(sm)
    assert occ["observability/kv_blocks_live"] == 8
    assert occ["observability/kv_live_bytes"] == 8 * 128 * 3072
    assert occ["observability/kv_window_blocks_live"] == 8
    assert occ["observability/kv_window_live_bytes"] == 8 * 128 * 6912
    assert sm.release_window(seq) == (1024 - 513 + 1) // 128 == 4
    occ = kv_occupancy(sm)
    assert occ["observability/kv_window_blocks_live"] == 4
    assert occ["observability/kv_window_live_bytes"] == 4 * 128 * 6912
    assert occ["observability/kv_live_bytes"] == 8 * 128 * 3072


def test_a_group_without_a_row_keeps_the_models_row():
    """``kv_groups`` beside ``kv_row`` with no row of the group's own: the
    window layers keep the model's row (and Trinity's k / v pools, above,
    read as before)."""
    sm = _row_manager(groups={"window": {"layers": [1], "window": 513}})
    kv = sm.kv_cache
    assert set(kv.cache["layer_1"]) == {"ckv", "idx_k"}
    assert kv.window_layer_token_bytes == kv.layer_token_bytes == 1536
    plain = _manager()
    assert plain.kv_cache.window_row is None
    assert plain.kv_cache.window_layer_token_bytes \
        == plain.kv_cache.layer_token_bytes == 2 * 2 * 16 * 4


def test_admission_counts_the_window_tables_bound_at_w513():
    sm = _row_manager(seqs=2, blocks=512)
    assert sm.window_table_bound == 14
    assert sm.window_blocks_needed(None, 49152) == 14
    assert sm.window_blocks_needed(None, 1024) == 8
    seq = sm.get_or_create_sequence(7)
    sm.maybe_allocate_kv(seq, 1024)
    seq.seen_tokens = 1024
    # the next chunk of 1,024 ends at entry 16 and its band starts at 4:
    # 12 blocks, of which the 4 that outlive the release are held
    assert sm.window_blocks_needed(seq, 1024) == 16 - 4 - 4
    sm.release_window(seq)
    assert (seq.win_first, len(seq.win_blocks)) == (4, 4)
    assert sm.window_blocks_needed(seq, 1) == 1
