"""One stored layout for a float K/V pool (PR 41): ``[rows, Hkv*D]`` wherever
the row is whole 128-lane tiles, the form both kernels the serving cells run
read as it lies.  Engines at 128-wide heads (a Mistral-shaped Llama with a
sliding window, group size 2; an OLMoE-shaped Mixtral, group size 1) on that
pool: the chip's route in interpret mode (the decode walk, every KV head in
one pair of dots a step, and the tiled kernel) against the XLA reads, and
the paths that move or
re-read pool rows (copy-on-write, gather -> scatter, int8 KV, ``verify_step``,
TP=2) still agree with themselves."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (RaggedLlama,
                                                              RaggedMixtral)
from deepspeed_tpu.inference.v2.modules import attention
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
from deepspeed_tpu.parallel import groups

BLOCK, TILE = 8, 16
#: name -> (ragged model, config, flax model): 128-wide heads, two KV heads
SHAPES = {
    "mistral": (RaggedLlama, LlamaConfig.tiny(
        hidden_size=512, num_attention_heads=4, num_key_value_heads=2,
        sliding_window=24, dtype=jnp.float32), LlamaForCausalLM),
    "olmoe": (RaggedMixtral, MixtralConfig.tiny(
        hidden_size=256, num_attention_heads=2, num_key_value_heads=2,
        dtype=jnp.float32), MixtralForCausalLM),
}


@pytest.fixture(scope="module")
def weights():
    return {name: flax(cfg).init(jax.random.key(3),
                                 np.zeros((1, 4), np.int32))["params"]
            for name, (_, cfg, flax) in SHAPES.items()}


def _engine(name, params, budget=64, **kv):
    ragged, cfg, _ = SHAPES[name]
    groups.initialize_mesh(model_parallel_size=1)
    eng = InferenceEngineV2(
        ragged(cfg, BLOCK), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": budget,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": BLOCK, **kv}}))
    eng.PREFILL_TILE = TILE
    return eng


def _prompts(name, lens=(21, 9)):
    rng = np.random.default_rng(5)
    return [rng.integers(0, SHAPES[name][1].vocab_size, size=(n,)).tolist()
            for n in lens]


def _put_then_decode(eng, prompts, steps=3):
    """Logits of one ``put`` of the prompts (chunks in the tile segment),
    of a mixed ``put`` (a decode beside a new prompt's chunk), then of
    ``steps`` greedy ``decode_step``s."""
    uids = list(range(len(prompts)))
    first = eng.put(uids, prompts)
    out = [np.asarray(first[u], np.float32) for u in uids]
    tok = [int(np.argmax(first[u])) for u in uids]
    mixed = eng.put([uids[0], 7], [[tok[0]], prompts[0][:11]])
    out += [np.asarray(mixed[u], np.float32) for u in (uids[0], 7)]
    tok[0] = int(np.argmax(mixed[uids[0]]))
    nxt = tok
    for _ in range(steps):
        logits, nxt = eng.decode_step(uids, nxt, greedy=True)
        out.append(np.asarray(logits, np.float32)[:len(uids)])
    eng.flush(uids + [7])
    return out


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_put_and_decode_step_on_the_flat_pool_match_the_xla_reads(
        monkeypatch, weights, name):
    """The route the chip takes (the decode walk and the tiled kernel, in
    interpret mode, handed the pool as it is stored) gives the logits of
    the XLA reads on the same pool."""
    from deepspeed_tpu.inference.v2 import kernels

    hkv, d = SHAPES[name][1].num_key_value_heads, SHAPES[name][1].head_dim
    assert d == 128
    want = _put_then_decode(_engine(name, weights[name]), _prompts(name))

    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    pools = {"paged_decode_attention": [], "paged_prefill_attention": []}
    for kernel in pools:
        def spy(q, k_pool, *a, _f=getattr(kernels, kernel), _n=kernel, **kw):
            pools[_n].append(k_pool.shape)
            return _f(q, k_pool, *a, **kw)
        monkeypatch.setattr(kernels, kernel, spy)
    eng = _engine(name, weights[name])
    for leaves in eng.state_manager.kv_cache.cache.values():
        assert leaves["k"].shape[1:] == leaves["v"].shape[1:] == (hkv * d,)
    got = _put_then_decode(eng, _prompts(name))
    # both kernels ran, on the 2-D pool the cache holds
    assert pools["paged_decode_attention"] and \
        pools["paged_prefill_attention"]
    assert {len(s) for shapes in pools.values() for s in shapes} == {2}
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=0)
        assert np.array_equal(np.argmax(g, -1), np.argmax(w, -1))


def test_block_operations_carry_the_flat_row(weights):
    """``copy_block`` and ``gather_blocks`` -> ``scatter_blocks`` move
    whole rows of whatever lanes: after a gather from one engine and a
    scatter into a fresh one the decode logits are the donor's."""
    name = "mistral"
    prompt = _prompts(name)[0]
    donor = _engine(name, weights[name])
    first = donor.put([0], [prompt])
    tok = int(np.argmax(first[0]))
    kv = donor.state_manager.kv_cache
    blocks = list(donor.state_manager.get_sequence(0).blocks)
    payload = kv.gather_blocks(blocks)
    hkv, d = SHAPES[name][1].num_key_value_heads, SHAPES[name][1].head_dim
    assert payload["layer_0"]["k"].shape == (len(blocks) * BLOCK, hkv * d)
    want = np.asarray(donor.decode_step([0], [tok]), np.float32)[0]

    # copy-on-write: the copy of a block equals the block, row for row
    spare = donor.state_manager.allocator.allocate(1)[0]
    kv.copy_block(blocks[0], spare)
    again = kv.gather_blocks([blocks[0], spare])["layer_1"]["v"]
    np.testing.assert_array_equal(again[:BLOCK], again[BLOCK:])

    taker = _engine(name, weights[name])
    taker.put([0], [prompt])             # same positions, its own blocks
    mine = list(taker.state_manager.get_sequence(0).blocks)
    zeros = jax.tree_util.tree_map(np.zeros_like, payload)
    taker.state_manager.kv_cache.scatter_blocks(mine, zeros)
    wiped = np.asarray(taker.decode_step([0], [tok]), np.float32)[0]
    assert np.max(np.abs(wiped - want)) > 1e-3
    taker2 = _engine(name, weights[name])
    taker2.put([0], [prompt])
    taker2.state_manager.kv_cache.scatter_blocks(
        list(taker2.state_manager.get_sequence(0).blocks), payload)
    got = np.asarray(taker2.decode_step([0], [tok]), np.float32)[0]
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    with pytest.raises(ValueError, match="cache geometry"):
        taker2.state_manager.kv_cache.scatter_blocks(
            mine, jax.tree_util.tree_map(
                lambda a: a.reshape(a.shape[0], hkv, d), payload))


def test_int8_kv_keeps_its_heads_apart_and_serves(weights):
    """An int8 pool stays ``[rows, Hkv, D]`` beside its scale a head, and a
    128-wide model still serves on it, close to the float pool."""
    name = "mistral"
    hkv, d = SHAPES[name][1].num_key_value_heads, SHAPES[name][1].head_dim
    prompts = _prompts(name)
    want = _put_then_decode(_engine(name, weights[name]), prompts)
    eng = _engine(name, weights[name], dtype="int8")
    leaves = eng.state_manager.kv_cache.cache["layer_0"]
    assert leaves["k"].shape[1:] == (hkv, d) and leaves["k"].dtype == jnp.int8
    assert leaves["k_scale"].shape[1:] == (hkv,)
    got = _put_then_decode(eng, prompts)
    assert np.array_equal(np.argmax(got[0], -1), np.argmax(want[0], -1))
    for g, w in zip(got, want):
        assert np.max(np.abs(g - w)) <= 0.05 * np.max(np.abs(w))


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_verify_step_on_the_flat_pool_matches_sequential_decode(
        monkeypatch, weights, route):
    """K rows a sequence in one forward give the logits of K decode steps;
    on the chip's route the verify kernel takes the pool's per-head view."""
    name = "mistral"
    if route == "kernels":
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
    prompt = _prompts(name)[0]
    eng = _engine(name, weights[name])
    toks = [int(np.argmax(eng.put([0], [prompt])[0]))]
    seq = []
    for _ in range(3):
        seq.append(np.asarray(eng.decode_step([0], [toks[-1]]),
                              np.float32)[0])
        toks.append(int(np.argmax(seq[-1])))
    eng2 = _engine(name, weights[name])
    assert int(np.argmax(eng2.put([0], [prompt])[0])) == toks[0]
    rows = np.asarray(eng2.verify_step([0], [toks[:3]]), np.float32)[0]
    for k in range(3):
        np.testing.assert_allclose(rows[k], seq[k], atol=3e-5, rtol=0)


def test_tp2_serving_splits_the_flat_row_by_kv_head(weights):
    """Under TP=2 the flat row is split into lane ranges, a shard its KV
    heads' lanes; the tokens are the TP=1 engine's."""
    name = "mistral"
    ragged, cfg, _ = SHAPES[name]
    prompts = _prompts(name, lens=(9, 5))
    want = _engine(name, weights[name], budget=24).generate(
        prompts, max_new_tokens=5)
    topo = groups.initialize_mesh(model_parallel_size=2)
    eng = InferenceEngineV2(
        ragged(cfg, BLOCK, mesh=topo.mesh), weights[name],
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 24,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": BLOCK}}))
    k = eng.state_manager.kv_cache.cache["layer_0"]["k"]
    lanes = cfg.num_key_value_heads * cfg.head_dim
    assert k.shape[1:] == (lanes,)
    assert {s.data.shape[1] for s in k.addressable_shards} == {lanes // 2}
    got = eng.generate(prompts, max_new_tokens=5)
    groups.initialize_mesh(model_parallel_size=1)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
