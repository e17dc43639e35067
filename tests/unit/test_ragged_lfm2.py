"""LFM2-MoE (``model_type: lfm2_moe``) through the normal serving path at a
small size on the CPU: ``RaggedLfm2`` -> ``InferenceEngineV2`` (``put``,
``decode_step``, two-segment batches, the state slot pool with a conv-only
leaf, a flat pool row) -> ``ContinuousBatchScheduler``, against the
benchmark's plain float32 reference (``benchmark/reference/lfm2_moe.py``:
a padded convolution over the whole sequence, no cache, no state).

Everything that makes the model what it is is drawn away from its neutral
value so that leaving it out fails: norm weights uniform in 0.5 .. 1.5, the
router N(0, 4/H), a selection bias of 0.3 N(0, 1) against scores that spread
by about 0.2.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _path in (_REPO, os.path.join(_REPO, "tools")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchmark.families import lfm2_moe as family           # noqa: E402
from benchmark.reference import lfm2_moe as reference       # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import blocked_flash  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_lfm2 as rl)
from deepspeed_tpu.inference.v2.modules import attention, conv  # noqa: E402
from deepspeed_tpu.inference.v2.modules.moe import (         # noqa: E402
    dropless_moe, moe_router)
from deepspeed_tpu.inference.v2.ragged import CacheLayoutError  # noqa: E402
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)

# the published keys at the test's size: 2 dense + 4 MoE layers in the
# published pattern (attention at layer 2)
HF = {"model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_hidden_layers": 6,
      "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                      "conv"],
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
      "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
      "use_expert_bias": True, "routed_scaling_factor": 1,
      "norm_eps": 1e-5, "max_position_embeddings": 512,
      "rope_parameters": {"rope_theta": 10000, "rope_type": "default"}}
MAX_SEQS, BUDGET, TILE, BLOCK = 72, 80, 16, 8

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same float32 mathematics in another
# order (chunks through flat ragged rows, a tail in a slot, a paged cache
# against one padded pass).  Measured 6e-7 .. 1.2e-6 here; 1e-4 is ~100x that
# and far below what a lost tail moves the logits by (the negative cases
# below: 0.4 or more).
F32_TOL = 1e-4
# bf16 engine (weights, activations, KV pool, convolution tail) against the
# float32 reference on the same bf16-rounded weights, on scaled-residual
# weights (``_seeded_params``): bf16 activation roundings and the routings
# they flip.  Measured here over six seeds: 0.0029 .. 0.0100.  0.03 is the
# benchmark's own limit for a bf16 engine (``LOGIT_TOL`` of
# ``runners/serve_ragged.py``).  (On the weights of ``_params``, drawn to
# make every fault loud, one flipped routing of top-2 of 8 experts at hidden
# 64 moves the logits by tenths: 0.024 .. 0.38 over four seeds; the float32
# cases are the ones held to those weights.)
BF16_TOL = 0.03


def _config(dtype, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rl.param_shapes(_config(jnp.float32, hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] == "e_score_correction_bias":
            a = 0.3 * rng.standard_normal(shape)
        elif names[-1] == "embedding":
            a = rng.standard_normal(shape)
        elif names[-1] in ("w_gate", "w_up", "w_down"):
            a = rng.standard_normal(shape) * shape[1] ** -0.5
        elif "wg" in names:
            a = 2.0 * rng.standard_normal(shape) * shape[0] ** -0.5
        else:
            a = rng.standard_normal(shape) * shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _seeded_params(seed=0):
    """Scaled-residual weights at the test's size: kernels N(0, 1/fan_in),
    the residual-writing ones at 1/sqrt(2 L), the routed experts' at a
    quarter of that again (at hidden 64 one flipped routing of top-2 of 8
    would otherwise carry the comparison), norm weights 1, the bias 0.1."""
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rl.param_shapes(_config(jnp.float32)))
    residual = (2 * HF["num_hidden_layers"]) ** -0.5
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape, a = leaf.shape, rng.standard_normal(leaf.shape)
        if names[-1] == "scale":
            a = np.ones(shape)
        elif names[-1] == "e_score_correction_bias":
            a *= 0.1
        elif names[-1] == "w_down":
            a *= 0.25 * residual * shape[1] ** -0.5
        elif names[-1] in ("w_gate", "w_up"):
            a *= shape[1] ** -0.5
        elif names[-2] in ("out_proj", "o_proj", "down_proj"):
            a *= residual * shape[0] ** -0.5
        elif names[-1] != "embedding":
            a *= shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _engine(params, dtype=jnp.float32, hf=HF, blocks=160, max_context=256,
            max_seqs=MAX_SEQS, **kv):
    model = rl.RaggedLfm2(_config(dtype, hf), BLOCK)
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(dtype), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks, **kv}}))
    eng.PREFILL_TILE = TILE          # an 80-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt, uid=7, chunks=None):
    """``put`` the prompt (in the given chunk sizes, or as the engine
    splits it), then decode the rest teacher-forced."""
    if chunks is None:
        row = eng.put([uid], [ids[:n_prompt].tolist()])[uid]
    else:
        assert sum(chunks) == n_prompt
        at = 0
        for n in chunks:
            row = eng.put([uid], [ids[at:at + n].tolist()])[uid]
            at += n
    got = [np.asarray(row, np.float32)]
    for t in ids[n_prompt:]:
        row = eng.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(params, ids, n_prompt, hf=HF):
    return reference.logits_at(family.reference_params(params), ids, hf,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _tails(eng):
    """Every live slot's tails on the host (the scratch slot left out)."""
    pool = eng.state_manager.state_pool
    return {k: np.asarray(v["conv"])[:pool.num_slots]
            for k, v in eng.state_manager.kv_cache.cache.items()
            if "conv" in v}


# ------------------------------------------------------------------ #
# (a) one prompt in 1, 2 and 5 chunks, among them chunks of one and of two
# rows, then 6 decode steps
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, chunks", [
    (40, None), (100, None), (330 - 6, None), (66, [64, 2]), (65, [64, 1]),
    (70, [3, 1, 2, 63, 1])],
    ids=["1_chunk", "2_chunks", "5_chunks", "a_chunk_of_two_rows",
         "a_chunk_of_one_row", "chunks_shorter_than_the_taps"])
def test_f32_engine_matches_reference(n_prompt, chunks):
    params, ids = _params(), _ids(n_prompt + 6)
    eng = _engine(params, blocks=48, max_context=352)
    assert _gap(_serve(eng, ids, n_prompt, chunks=chunks),
                _want(params, ids, n_prompt)) <= F32_TOL
    assert eng.state_manager.state_pool.held == 0


def test_bf16_engine_is_the_same_model():
    params, ids = _seeded_params(), _ids(100 + 6)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = _serve(_engine(params, jnp.bfloat16), ids, 100)
    assert _gap(got, _want(rounded, ids, 100)) <= BF16_TOL


@pytest.mark.parametrize("fault", ["tail_zeroed", "silu_left", "b_c_swapped",
                                   "bias_dropped"])
def test_a_seeded_fault_fails_the_tolerance(fault, monkeypatch):
    """The negative cases (the four the chip check is held to): the tail
    zeroed at every chunk boundary, SiLU left after the taps, B and C
    exchanged, the selection bias dropped."""
    params, ids = _params(), _ids(66 + 6)
    real_conv = conv._causal_conv
    if fault == "tail_zeroed":
        monkeypatch.setattr(rl, "_causal_conv", lambda u, w, pool, batch,
                            **kw: real_conv(
            u, w, jnp.zeros_like(pool), batch, **kw))
    elif fault == "silu_left":
        monkeypatch.setattr(rl, "_causal_conv", lambda u, w, pool, batch,
                            activation=None, **kw: real_conv(
            u, w, pool, batch, **kw))
    elif fault == "b_c_swapped":
        params = jax.tree.map(lambda a: a, params)
        for i in range(HF["num_hidden_layers"]):
            cv = params[f"layers_{i}"].get("conv")
            if cv:
                b, c, u = jnp.split(cv["in_proj"]["kernel"], 3, axis=1)
                cv["in_proj"] = {"kernel": jnp.concatenate([c, b, u], 1)}
    want = _want(_params(), ids, 66)
    if fault == "bias_dropped":
        params = jax.tree_util.tree_map_with_path(
            lambda p, a: jnp.zeros_like(a) if "bias" in str(p[-1]) else a,
            params)
    got = _serve(_engine(params), ids, 66, chunks=[64, 2])
    assert _gap(got, want) > 100 * F32_TOL


# ------------------------------------------------------------------ #
# (b) six sequences interleaved through the scheduler at more than 64
# slots: joins, a flush, a reused slot, a preemption by recompute
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


PROMPT_LENS, NEW = (150, 40, 90, 7, 33, 65), (4, 9, 5, 12, 6, 5)


@pytest.fixture(scope="module")
def served():
    params = _params()
    prompts = [_ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    return params, prompts


def test_interleaved_logits_match_each_reference(served):
    from interleaved_logits import serve_and_compare

    params, prompts = served
    eng = _engine(params)
    assert eng._batch.max_seqs > 64
    out = serve_and_compare(eng, reference, family.reference_params(params),
                            HF, prompts, NEW)
    assert len(out["gaps"]) == 6 and max(out["gaps"]) <= F32_TOL, out
    assert eng.state_manager.state_pool.held == 0


def test_a_reused_slot_starts_from_zero(served):
    """Three slots, six requests: the later ones take slots the earlier
    ones left, whose tails are whatever they held (a slot is never cleared
    on release; a chunk that starts at position 0 reads zeros)."""
    from interleaved_logits import serve_and_compare

    params, prompts = served
    eng = _engine(params, max_seqs=3)
    out = serve_and_compare(eng, reference, family.reference_params(params),
                            HF, prompts, NEW)
    assert max(out["gaps"]) <= F32_TOL, out
    assert any(np.abs(t).max() > 0 for t in _tails(eng).values())
    assert eng.state_manager.state_pool.free == 3


def test_preemption_by_recompute_gives_the_same_tokens(served):
    params, prompts = served

    def solo(p, n):
        sched = ContinuousBatchScheduler(_engine(params))
        req = sched.submit(list(p), _greedy(n))
        sched.run_until_idle()
        return list(req.generated)

    news = (30, 25, 40, 30)
    # 23 usable blocks of 8 tokens: the four requests together outgrow
    # them while decoding, so the newest is preempted and recomputed
    eng = _engine(params, blocks=24)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts[1:5], news)]
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    assert [list(r.generated) for r in reqs] == [
        solo(p, n) for p, n in zip(prompts[1:5], news)]
    assert eng.state_manager.state_pool.held == 0


# ------------------------------------------------------------------ #
# (c) pad rows write no slot (``_causal_conv`` itself against a plain
# convolution: ``test_causal_conv.py``)
# ------------------------------------------------------------------ #
def test_pad_rows_and_padded_tails_change_no_other_slot():
    params = _params()
    eng = _engine(params, max_seqs=4)
    eng.put([1], [_ids(30, seed=1).tolist()])
    eng.put([2], [_ids(50, seed=2).tolist()])
    s1, s2 = (eng.state_manager.get_sequence(u).state_slot for u in (1, 2))
    before = _tails(eng)
    eng.decode_step([1], [5])       # three pad rows beside it
    eng.put([3], [_ids(21, seed=3).tolist()])   # a tile with 11 pad rows
    s3 = eng.state_manager.get_sequence(3).state_slot
    for layer, a in before.items():
        b = _tails(eng)[layer]
        assert np.array_equal(a[s2], b[s2]), layer          # bitwise
        assert not np.array_equal(a[s1], b[s1])
        untouched = [s for s in range(4) if s not in (s1, s3)]
        assert np.array_equal(a[untouched], b[untouched])


# ------------------------------------------------------------------ #
# (d) the router: a hand-computed case where the bias changes the
# selection, with the 1e-6; every expert held gives the uncut sum
# ------------------------------------------------------------------ #
def test_router_bias_selects_and_does_not_weigh():
    # one token, four experts, scores sigmoid(logit): expert 3 has the
    # lowest score and the largest bias
    x = jnp.asarray([[1.0, 0.0]], jnp.float32)
    wg = jnp.asarray([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.7], jnp.float32)
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))
    idx, w = moe_router(x, wg, 2, True, bias=bias, norm_eps=1e-6)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]   # not [0, 1]
    want = {0: s[0] / (s[0] + s[3] + 1e-6), 3: s[3] / (s[0] + s[3] + 1e-6)}
    for e, we in zip(np.asarray(idx)[0], np.asarray(w)[0]):
        assert abs(we - want[int(e)]) < 1e-7
    # the constant is the argument's: the default stays the router's own
    _, w20 = moe_router(x, wg, 2, True, bias=bias)
    assert abs(float(w20.sum()) - 1.0) < 1e-7 < 1.0 - float(w.sum())
    idx0, _ = moe_router(x, wg, 2, True, bias=jnp.zeros(4))
    assert sorted(np.asarray(idx0)[0].tolist()) == [0, 1]


def test_every_expert_held_gives_the_uncut_sum():
    rng = np.random.default_rng(4)
    h, f, e, k, t = 64, 32, 8, 2, 50
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x, router, bias = f32(t, h), 2.0 * f32(h, e) * h ** -0.5, 0.3 * f32(e)
    lp = {"router": router, "bias": bias, "w_gate": f32(e, h, f) * h ** -0.5,
          "w_up": f32(e, h, f) * h ** -0.5, "w_down": f32(e, f, h) * f ** -0.5}
    moe = {"gate": {"wg": {"kernel": router},
                    "e_score_correction_bias": bias},
           "experts": {k_: lp[k_] for k_ in ("w_gate", "w_up", "w_down")}}
    got = np.asarray(dropless_moe(x, moe, k, jnp.float32, norm_eps=1e-6))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.routed(
            x, lp, top_k=k, norm_topk=True, scale=1.0, norm_eps=1e-6,
            expert_start=0))
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))


# ------------------------------------------------------------------ #
# (e) the walks on the flat pool row (interpret mode) against the XLA
# oracle: narrow heads packed to a lane tile, and the heads of whole tiles
# every float pool is stored flat for since PR 41
# ------------------------------------------------------------------ #
def _flat_case(h, hkv, d, bs, seed=3):
    s_count, b = 6, 5
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    nb = s_count * b + 1
    tables = jnp.asarray(
        rng.permutation(np.arange(1, s_count * b + 1)).reshape(s_count, b),
        jnp.int32)
    return f(nb * bs, hkv * d), f(nb * bs, hkv * d), tables


# the whole-tile heads of the serving cells (Mistral, OLMoE, Trinity with
# and without its window, Qwen3-Next): (h, hkv, d, block size, window)
WHOLE_TILE = [(32, 8, 128, 16, None), (16, 16, 128, 16, None),
              (48, 8, 128, 16, None), (48, 8, 128, 16, 40),
              (16, 2, 256, 16, None)]
WHOLE_TILE_IDS = ["mistral_32q_8kv_d128", "olmoe_16q_16kv_d128",
                  "trinity_48q_8kv_d128", "trinity_window",
                  "qwen3next_16q_2kv_d256"]


@pytest.mark.parametrize("h, hkv, d, bs, window", [
    (32, 8, 64, 128, None), (8, 8, 64, 16, None), (16, 2, 64, 16, None),
    (8, 4, 32, 16, None)] + WHOLE_TILE,
    ids=["32q_8kv_d64", "multi_head_d64", "multi_query_groups_d64",
         "four_heads_a_tile_d32"] + WHOLE_TILE_IDS)
def test_decode_walk_on_a_flat_row_matches_the_oracle(h, hkv, d, bs, window):
    kp, vp, tables = _flat_case(h, hkv, d, bs)
    b = tables.shape[1]
    # ragged positions: a first token, a block's last row, the next block's
    # first, a sequence that crosses blocks, a pad row, a full table
    pos = jnp.asarray([0, bs - 1, bs, 3 * bs + 5, -1, b * bs - 1], jnp.int32)
    slot = jnp.asarray([3, 1, 0, 5, 0, 2], jnp.int32)
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (6, h, d)), jnp.float32)
    got = blocked_flash.paged_decode_attention(
        q, kp, vp, tables, slot, pos, block_size=bs, window=window,
        interpret=True)
    batch = {"block_tables": tables, "token_slot": slot, "token_pos": pos}
    want = attention._paged_attention(q, kp, vp, batch, bs,
                                         use_kernel=False, decode_mode=True,
                                         window=window)
    live = np.asarray(pos) >= 0
    assert np.max(np.abs(np.asarray(got - want))[live]) <= 1e-5
    assert np.all(np.asarray(got)[~live] == 0)
    if window is not None:              # the window bites on the long rows
        full = attention._paged_attention(
            q, kp, vp, batch, bs, use_kernel=False, decode_mode=True)
        assert np.max(np.abs(np.asarray(want - full))[live]) > 1e-3


@pytest.mark.parametrize("h, hkv, d, bs, window", [
    (32, 8, 64, 128, None), (8, 8, 64, 16, None)] + WHOLE_TILE,
    ids=["32q_8kv_d64", "multi_head_d64"] + WHOLE_TILE_IDS)
def test_tiled_prefill_on_a_flat_row_matches_the_oracle(h, hkv, d, bs,
                                                        window):
    kp, vp, tables = _flat_case(h, hkv, d, bs, seed=6)
    tile = bs
    t_rows = 3 * tile
    slot, pos = np.zeros((t_rows,), np.int32), np.full((t_rows,), -1,
                                                       np.int32)
    slot[:tile + 5], pos[:tile + 5] = 1, np.arange(7, 7 + tile + 5)
    slot[2 * tile:], pos[2 * tile:] = 3, np.arange(tile)
    q = jnp.asarray(np.random.default_rng(2).standard_normal(
        (t_rows, h, d)), jnp.float32)
    got = blocked_flash.paged_prefill_attention(
        q, kp, vp, tables, jnp.asarray(slot), jnp.asarray(pos),
        block_size=bs, tile_q=tile, window=window, interpret=True)
    want = attention._paged_attention(
        q, kp, vp, {"block_tables": tables, "token_slot": jnp.asarray(slot),
                    "token_pos": jnp.asarray(pos)}, bs, use_kernel=False,
        window=window)
    assert np.max(np.abs(np.asarray(got - want))[pos >= 0]) <= 1e-5


def test_walk_rule_is_what_the_kernels_take():
    usable = blocked_flash.decode_walk_usable
    z = lambda *s, dt=jnp.bfloat16: jnp.zeros(s, dt)
    # the flat row of whole tiles: heads of whole tiles, heads that divide one
    assert usable(128, z(64, 1024)) and usable(256, z(64, 512))
    assert usable(64, z(64, 512)) and usable(32, z(64, 128))
    # a float pool kept per head (its row is no whole tiles) is not walked,
    # and the kernel refuses one handed to it in that form
    assert not usable(64, z(64, 1, 64)) and not usable(128, z(64, 8, 128))
    assert not usable(64, z(64, 192))         # a row that is no whole tiles
    assert not usable(48, z(64, 384))         # a head that divides no tile
    # int8 keeps [rows, Hkv, D] beside its scales, walked at whole-tile heads
    assert usable(128, z(64, 8, 128, dt=jnp.int8))
    assert not usable(64, z(64, 8, 64, dt=jnp.int8))
    assert not usable(64, z(64, 512, dt=jnp.int8))
    with pytest.raises(ValueError, match="flat row"):
        blocked_flash.paged_decode_attention(
            z(2, 8, 128), z(64, 2, 128), z(64, 2, 128),
            jnp.zeros((2, 4), jnp.int32), jnp.arange(2), jnp.arange(2),
            block_size=16, interpret=True)


@pytest.mark.parametrize("dtype, hkv, d, row", [
    ("bf16", 8, 128, (1024,)), ("bf16", 16, 128, (2048,)),
    ("bf16", 2, 256, (512,)), ("bf16", 8, 64, (512,)),
    ("float32", 2, 64, (128,)), ("bf16", 1, 64, (1, 64)),
    ("bf16", 3, 96, (3, 96)), ("bf16", 32, 96, (32, 96)),
    ("bf16", 32, 80, (32, 80)), ("int8", 8, 128, (8, 128))],
    ids=["mistral", "olmoe", "qwen3next", "lfm2_widths", "two_heads_d64",
         "one_head_d64", "no_whole_tiles", "whole_tiles_of_d96_heads",
         "whole_tiles_of_d80_heads", "int8"])
def test_one_stored_layout_decided_from_dtype_and_shapes(dtype, hkv, d, row):
    """A float pool whose row is whole 128-lane tiles, at heads the decode
    walk reads in that row, is stored flat for every model; the rule looks
    at the dtype, ``Hkv`` and ``D`` alone, and the walk asks the same rule:
    no pool is stored flat for a read that copies it back to heads."""
    from deepspeed_tpu.inference.v2.kernels import decode_walk_usable
    from deepspeed_tpu.inference.v2.ragged.kv_cache import (BlockedKVCache,
                                                            flat_row)

    kv = BlockedKVCache(2, 3, 16, hkv, d, dtype)
    assert flat_row(dtype, hkv, d) == (len(row) == 1)
    if not kv.quantized:
        assert decode_walk_usable(d, kv.cache["layer_0"]["k"]) \
            == (len(row) == 1)
    for leaves in kv.cache.values():
        assert leaves["k"].shape == leaves["v"].shape == (3 * 16,) + row
    itemsize = jnp.dtype(kv.dtype).itemsize
    assert kv.layer_token_bytes == 2 * hkv * (d * itemsize
                                              + 4 * kv.quantized)


def test_one_token_rows_of_the_engine_take_the_walk(monkeypatch):
    """The chip's route in interpret mode through the whole engine: the
    decode program's one-token rows on the flat pool go through
    ``_decode_kernel`` (counted), and the logits are the reference's."""
    calls = []
    real = blocked_flash.paged_decode_attention
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    import deepspeed_tpu.inference.v2.kernels as kernels
    monkeypatch.setattr(
        kernels, "paged_decode_attention",
        lambda q, k, *a, **kw: calls.append(k.shape) or real(q, k, *a, **kw))
    hf = {**HF, "num_attention_heads": 4, "num_key_value_heads": 2,
          "head_dim": 64, "hidden_size": 64}
    params, ids = _params(hf), _ids(40 + 3)
    got = _serve(_engine(params, hf=hf), ids, 40)
    assert _gap(got, _want(params, ids, 40, hf)) <= F32_TOL
    assert calls and all(len(shape) == 2 for shape in calls)


# ------------------------------------------------------------------ #
# (f) what a token and a sequence hold; the paths that skip or rewind
# positions refuse by name
# ------------------------------------------------------------------ #
def test_bytes_a_token_and_a_sequence_hold():
    eng = _engine(_params(), jnp.bfloat16)
    sm = eng.state_manager
    # the attention layer alone: 1 layer x (k + v) x 2 heads x 16 x 2 B
    assert sm.kv_cache.kv_layers == (2,)
    assert sm.kv_cache.per_token_bytes == 1 * 2 * 2 * 16 * 2
    # the five convolution layers' tails alone: 2 rows x 64 channels x 2 B,
    # flat in one row of a whole lane tile (``slot_bytes``; the published
    # 2 x 2,048 channels are whole tiles too: the next test)
    assert sm.state_pool.layers == (0, 1, 3, 4, 5)
    assert sm.state_pool.per_sequence_bytes == 5 * 2 * 64 * 2
    cache = sm.kv_cache.cache
    # 2 heads of 16 are no whole lane tile: the cache's rule (``flat_row``)
    # keeps heads apart; the published 8 x 64 is stored flat (the next test)
    assert set(cache["layer_2"]) == {"k", "v"} and \
        cache["layer_2"]["k"].shape == (160 * BLOCK, 2, 16)
    assert set(cache["layer_0"]) == {"conv"} and \
        cache["layer_0"]["conv"].shape == (MAX_SEQS + 1, 2 * 64)


def test_bytes_at_the_published_widths():
    """The cell's configuration: 2 attention layers x 2,048 B a token, 8
    convolution layers x 8 KB a sequence (shapes only, nothing allocated)."""
    import json

    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.ragged.state_pool import StateSlotPool

    with open(os.path.join(_REPO, "benchmark", "configs",
                           "lfm2-24b-a2b-serve-1chip.json")) as f:
        hf = json.load(f)
    model = family.serve_model(hf, 128)
    spec = model.state_spec
    pool = StateSlotPool(128, spec["layers"], spec["leaves"])
    assert pool.per_sequence_bytes == 8 * 8192 == \
        family.shapes(hf)["state_bytes_per_seq"]
    assert not hasattr(model, "kv_row")     # the cache's own rule
    kv = BlockedKVCache(10, 1, 128, 8, 64, kv_layers=[2, 6])
    assert kv.cache["layer_2"]["k"].shape == (128, 512)
    assert kv.per_token_bytes == 2 * 2048 == \
        family.shapes(hf)["kv_bytes_per_token"]


@pytest.mark.parametrize("path", [
    "prefix_cache", "verify_step", "decode_loop", "flush_to_host_kv",
    "resume_kv", "untiled_budget"])
def test_paths_that_cannot_carry_a_tail_refuse_by_name(path):
    params = _params()
    if path == "prefix_cache":
        with pytest.raises(CacheLayoutError, match="RaggedLfm2"):
            _engine(params, enable_prefix_cache=True)
        return
    eng = _engine(params)
    if path == "untiled_budget":
        eng.PREFILL_TILE = 48           # 80 is no whole number of tiles
        with pytest.raises(CacheLayoutError, match="whole tiles"):
            eng.put([1], [[1, 2, 3]])
        return
    eng.put([1], [_ids(20).tolist()])
    call = {
        "verify_step": lambda: eng.verify_step([1], [[3, 4]]),
        "decode_loop": lambda: eng.decode_loop([1], [3], 4),
        "flush_to_host_kv": lambda: eng.flush_to_host([1], include_kv=True),
        "resume_kv": lambda: eng.resume(
            9, list(range(8)), kv_state={"seen_tokens": 8, "kv": {}}),
    }[path]
    with pytest.raises(CacheLayoutError, match=path.split("_kv")[0]):
        call()
    assert eng.state_manager.get_sequence(1).seen_tokens == 20


# ------------------------------------------------------------------ #
# (g) the device scopes exist; state_slots, read_blocks and row_blocks
# match a hand count
# ------------------------------------------------------------------ #
def test_device_scopes_of_a_mixed_batch():
    eng = _engine(_params())
    eng.put([1], [_ids(20).tolist()])
    eng.put([1, 2], [[5], _ids(30, seed=2).tolist()])
    text = "\n".join(eng.lower_step(k).as_text(debug_info=True)
                     for k in eng.step_keys)
    for scope in ("layers_0/conv/in_proj", "layers_0/conv/mix",
                  "layers_0/conv/out_proj", "layers_2/attn/qkv",
                  "layers_2/attn/rope_insert", "layers_2/attn/out_proj",
                  "layers_0/mlp", "layers_1/mlp", "layers_2/moe/router",
                  "layers_2/moe/dispatch", "layers_2/moe/experts",
                  "layers_2/moe/combine", "lm_head"):
        assert scope in text, scope
    assert "layers_2/mlp" not in text and "layers_0/moe" not in text
    assert "moe/shared" not in text


def test_counters_match_a_hand_count():
    trc = Tracer()
    eng = _engine(_params())
    sched = ContinuousBatchScheduler(eng, tracer=trc)
    a = sched.submit(_ids(20).tolist(), _greedy(8))     # 3 blocks of 8
    while len(a.generated) < 3:
        sched.step()
    sched.submit(_ids(30, seed=2).tolist(), _greedy(2))
    sched.step()
    sched.run_until_idle()
    recs = trc.records()
    builds = [r["attrs"] for r in recs if r["name"] == "engine/build_batch"
              and r.get("attrs")]
    # the first batch: one chunk of 20 tokens, one slot held, no one-token
    # row; the mixed batch: sequence a's row beside the 30-token chunk
    assert builds[0]["chunk_seqs"] == 1 and builds[0]["chunk_tokens"] == 20
    assert builds[0]["state_slots"] == 1 and builds[0]["row_blocks"] == 0
    mixed = [b for b in builds if b["chunk_tokens"] == 30]
    assert len(mixed) == 1 and mixed[0]["state_slots"] == 2
    assert "attn_pairs" not in mixed[0]         # a latent row's alone
    assert 3 <= mixed[0]["row_blocks"] <= 4
    reads = [r["attrs"]["read_blocks"] for r in recs
             if r["name"] == "decode" and "read_blocks" in (r.get("attrs")
                                                            or {})]
    # a pure-decode tick of sequence a alone at position p reads p // 8 + 1
    assert reads and all(3 <= n <= 8 for n in reads)
    preps = [r["attrs"] for r in recs if r["name"] == "engine/decode_prep"
             and r.get("attrs")]
    assert preps and all(p["state_slots"] in (1, 2) for p in preps)


# ------------------------------------------------------------------ #
# (h) a checkpoint under the published tensor names, tied head included
# ------------------------------------------------------------------ #
def test_loader_on_a_synthetic_lfm2_moe_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, ``in_proj`` rows ``B | C | x``, ``conv.conv.weight``
    [channels, 1, taps], experts one by one as ``w1 / w3 / w2``,
    ``expert_bias``, a tied ``lm_head.weight`` beside the embedding) load
    into ``RaggedLfm2``'s tree, and the engine built from the directory
    serves the reference's logits, the reference fed the same tensors by
    their published meaning."""
    import json

    from safetensors.numpy import save_file

    from deepspeed_tpu.checkpoint.hf_loader import load_hf_checkpoint

    rng = np.random.default_rng(8)
    h, f, fd, e = 64, 32, 96, 8
    n = lambda *s: rng.standard_normal(s).astype(np.float32)
    sd = {"model.embed_tokens.weight": n(256, h),
          "model.embedding_norm.weight": rng.uniform(0.5, 1.5, h).astype(
              np.float32)}
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    ref_layers = []
    for i, kind in enumerate(HF["layer_types"]):
        pre = f"model.layers.{i}."
        sd[pre + "operator_norm.weight"] = rng.uniform(0.5, 1.5, h).astype(
            np.float32)
        sd[pre + "ffn_norm.weight"] = rng.uniform(0.5, 1.5, h).astype(
            np.float32)
        lp = {"ln1": sd[pre + "operator_norm.weight"],
              "ln2": sd[pre + "ffn_norm.weight"]}
        if kind == "conv":
            sd[pre + "conv.in_proj.weight"] = n(3 * h, h) * h ** -0.5
            sd[pre + "conv.conv.weight"] = n(h, 1, 3) * 3 ** -0.5
            sd[pre + "conv.out_proj.weight"] = n(h, h) * h ** -0.5
            lp.update(w_in=sd[pre + "conv.in_proj.weight"].T,
                      taps=sd[pre + "conv.conv.weight"][:, 0, :].T,
                      w_out=sd[pre + "conv.out_proj.weight"].T)
        else:
            for name, rows in (("q", 64), ("k", 32), ("v", 32)):
                sd[pre + f"self_attn.{name}_proj.weight"] = \
                    n(rows, h) * h ** -0.5
            sd[pre + "self_attn.out_proj.weight"] = n(h, 64) * 64 ** -0.5
            sd[pre + "self_attn.q_layernorm.weight"] = rng.uniform(
                0.5, 1.5, 16).astype(np.float32)
            sd[pre + "self_attn.k_layernorm.weight"] = rng.uniform(
                0.5, 1.5, 16).astype(np.float32)
            lp.update(wq=sd[pre + "self_attn.q_proj.weight"].T,
                      wk=sd[pre + "self_attn.k_proj.weight"].T,
                      wv=sd[pre + "self_attn.v_proj.weight"].T,
                      wo=sd[pre + "self_attn.out_proj.weight"].T,
                      q_norm=sd[pre + "self_attn.q_layernorm.weight"],
                      k_norm=sd[pre + "self_attn.k_layernorm.weight"])
        if i < HF["num_dense_layers"]:
            sd[pre + "feed_forward.w1.weight"] = n(fd, h) * h ** -0.5
            sd[pre + "feed_forward.w3.weight"] = n(fd, h) * h ** -0.5
            sd[pre + "feed_forward.w2.weight"] = n(h, fd) * fd ** -0.5
            lp.update(gate=sd[pre + "feed_forward.w1.weight"].T,
                      up=sd[pre + "feed_forward.w3.weight"].T,
                      down=sd[pre + "feed_forward.w2.weight"].T)
        else:
            sd[pre + "feed_forward.gate.weight"] = 2.0 * n(e, h) * h ** -0.5
            sd[pre + "feed_forward.expert_bias"] = 0.3 * n(e)
            for j in range(e):
                ex = pre + f"feed_forward.experts.{j}."
                sd[ex + "w1.weight"] = n(f, h) * h ** -0.5
                sd[ex + "w3.weight"] = n(f, h) * h ** -0.5
                sd[ex + "w2.weight"] = n(h, f) * f ** -0.5
            stack = lambda w: np.stack([
                sd[pre + f"feed_forward.experts.{j}.{w}.weight"].T
                for j in range(e)])
            lp.update(router=sd[pre + "feed_forward.gate.weight"].T,
                      bias=sd[pre + "feed_forward.expert_bias"],
                      w_gate=stack("w1"), w_up=stack("w3"),
                      w_down=stack("w2"))
        ref_layers.append(lp)
    save_file(sd, str(tmp_path / "model.safetensors"))
    with open(tmp_path / "config.json", "w") as fh:
        json.dump({**HF, "tie_embedding": True}, fh)

    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    want_shapes = jax.tree.map(lambda a: a.shape,
                               rl.param_shapes(_config(jnp.float32)))
    assert jax.tree.map(lambda a: a.shape, params) == want_shapes
    assert "lm_head" not in params                  # tied: the embedding

    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": 4,
                              "max_context": 256},
            "kv_cache": {"block_size": BLOCK, "num_blocks": 40}}),
        dtype=jnp.float32)
    assert type(eng.model) is rl.RaggedLfm2
    eng.PREFILL_TILE = TILE
    ids = _ids(40 + 4, seed=9)
    ref = jax.tree.map(jnp.asarray, {
        "embed": sd["model.embed_tokens.weight"], "layers": ref_layers,
        "norm": sd["model.embedding_norm.weight"]})
    want = reference.logits_at(ref, ids, HF, rows=list(range(39, 44)))
    assert _gap(_serve(eng, ids, 40), want) <= F32_TOL
