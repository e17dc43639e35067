"""GLM-5 (``model_type: glm_moe_dsa``) through the normal serving path at a
small size on the CPU: ``RaggedDeepseekV3`` with a low-rank query and a
learned sparse-attention indexer -> ``InferenceEngineV2`` (``put``,
``decode_step``, two-segment batches, a pool of TWO leaves a layer: the
latent row and the indexer key) -> ``ContinuousBatchScheduler``, against the
benchmark's plain float32 reference (``benchmark/reference/glm_moe_dsa.py``:
``I`` as a causal matrix, the selection by a stable sort, no cache).

``index_topk`` (24) is a fraction of every context here, so a program that
reads every row, or the wrong 24, fails; what makes the model what it is is
drawn away from its neutral value (norm weights uniform in 0.5 .. 1.5, the
indexer key's LayerNorm bias N(0, 0.3^2)) so that leaving it out fails.
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

from benchmark.families import glm_moe_dsa as family        # noqa: E402
from benchmark.families import moonlight as moonlight_family  # noqa: E402
from benchmark.reference import glm_moe_dsa as reference    # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import sparse_latent as sl  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_deepseek_v3 as rd)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe  # noqa: E402
from deepspeed_tpu.inference.v2.ragged.kv_cache import (     # noqa: E402
    BlockedKVCache)
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)
from glm_dsa_faults import fault                             # noqa: E402

TOPK = 24
HF = {"model_type": "glm_moe_dsa", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_hidden_layers": 3, "num_attention_heads": 4,
      "kv_lora_rank": 32, "q_lora_rank": 48, "qk_nope_head_dim": 24,
      "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
      "router_experts": 8, "expert_start": 2, "n_shared_experts": 1,
      "num_experts_per_tok": 2, "first_k_dense_replace": 1,
      "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
      "norm_topk_prob": True, "routed_scaling_factor": 2.5,
      "scoring_func": "sigmoid", "topk_method": "noaux_tc",
      "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
      "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
      "index_n_heads": 4, "index_head_dim": 128, "index_topk": TOPK}
MAX_SEQS, BUDGET, TILE, BLOCK = 8, 64, 16, 16
# the same float32 mathematics in another order (measured 6e-7 here)
F32_TOL = 1e-4
# the benchmark's own limit (``LOGIT_TOL`` of ``runners/serve_ragged.py``)
BF16_TOL = 0.03


def _config(dtype, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rd.param_shapes(_config(jnp.float32, hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] in ("bias", "e_score_correction_bias"):
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


def _ref_params(params):
    """The reference's dict of the program's own values (the family's
    seeded-bias mapping is the benchmark's, undone here)."""
    ref = family.reference_params(params)
    for lp in ref["layers"]:
        if "bias" in lp:
            lp["bias"] = (lp["bias"] - moonlight_family.BIAS_MEAN) \
                / moonlight_family.BIAS_STD
    return ref


def _engine(params, act=jnp.float32, hf=HF, blocks=120, max_context=512,
            max_seqs=MAX_SEQS, budget=BUDGET, tile=TILE, interpret=None,
            **kv):
    model = rd.RaggedDeepseekV3(_config(act, hf), BLOCK)
    # True: the tile rows' read through its Mosaic kernel, interpreted
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(act), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": budget,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks, **kv}}))
    eng.PREFILL_TILE = tile          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _decode(eng, ids, uid):
    return np.stack([
        np.asarray(jax.device_get(eng.decode_step([uid], [int(t)])),
                   np.float32)[0] for t in ids])


def _serve(eng, ids, n_prompt, uid=7):
    got = np.concatenate([
        np.asarray(eng.put([uid], [ids[:n_prompt].tolist()])[uid],
                   np.float32)[None], _decode(eng, ids[n_prompt:], uid)])
    eng.flush([uid])
    return got


def _want(params, ids, n_prompt, hf=HF):
    return reference.logits_at(_ref_params(params), ids, hf,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ------------------------------------------------------------------ #
# (a) engine against reference: chunks that cross index_topk, then decode
# through both leaves
# ------------------------------------------------------------------ #
@pytest.fixture
def short_key_steps(monkeypatch):
    """The kernel's key step at 64 positions: a context of 140 is three."""
    monkeypatch.setattr(sl, "_STEP_KEYS", 64)
    sl.sparse_tile_read.clear_cache()       # (traced at another step)
    yield
    sl.sparse_tile_read.clear_cache()


@pytest.mark.parametrize("n_prompt, tile, budget, interpret", [
    (20, TILE, BUDGET, None),   # under index_topk: every row reads all
    (140, TILE, BUDGET, None),  # three chunks, the masked read over tiles
    (140, 128, 60, None),       # no tiles: rows packed back to back
    (140, TILE, BUDGET, True),  # the tiles' read through the kernel
])
def test_f32_engine_matches_reference(n_prompt, tile, budget, interpret,
                                      short_key_steps):
    params = _params()
    ids = _ids(n_prompt + 6)
    eng = _engine(params, tile=tile, budget=budget, interpret=interpret)
    assert (eng._prefill_tile() is None) == (tile == 128)
    assert _gap(_serve(eng, ids, n_prompt),
                _want(params, ids, n_prompt)) <= F32_TOL


@pytest.mark.parametrize("seed", [0, 2])
def test_bf16_engine_is_the_same_model(seed):
    """bf16 engine against the float32 reference on the same bf16-rounded
    weights, ``o_proj`` at the family's ``ATTN_OUT``: a top-k is a
    discontinuity, and a position whose index score lies within a bf16
    rounding of the 24th is chosen otherwise than in float32; at ``o_proj``
    x 1 one such swap of 24 moved the logits by 0.05-0.2 here, at x 0.3 the
    two seeds read 0.020 and 0.012.  (Seed 1 reads 0.05-0.08 with the
    indexer and with ``index_topk`` over the context alike: a routing flip
    of this tiny router, not the indexer's.)"""
    params = _params(seed=seed)
    for i in range(HF["num_hidden_layers"]):
        o_proj = params[f"layers_{i}"]["self_attn"]["o_proj"]
        o_proj["kernel"] = o_proj["kernel"] * family.ATTN_OUT
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    ids = _ids(146, seed=seed + 3)
    got = _serve(_engine(params, act=jnp.bfloat16), ids, 140)
    assert _gap(got, _want(params, ids, 140)) <= BF16_TOL


def test_the_sparse_read_is_not_the_dense_one():
    """The reference at ``index_topk`` >= the context is another model."""
    params = _params()
    ids = _ids(146)
    dense = _want(params, ids, 140, dict(HF, index_topk=4096))
    assert _gap(dense, _want(params, ids, 140)) > 0.1


def test_a_topk_over_the_context_is_the_dense_latent_model():
    """``index_topk`` >= every context selects every cached position: the
    logits are those of the same weights served WITHOUT an indexer
    (``index_topk`` None: the dense latent read behind a low-rank query)."""
    params = _params()
    ids = _ids(146)
    all_of_it = dict(HF, index_topk=1000)
    got = _serve(_engine(params, hf=all_of_it), ids, 140)
    assert _gap(got, _want(params, ids, 140, all_of_it)) <= F32_TOL
    dense_hf = dict(HF, index_topk=None)
    cfg = moonlight_family.program_config(
        {**dense_hf, "rope_theta": 1000000})
    cfg.dtype = jnp.float32
    assert cfg.index_topk is None and cfg.q_lora_rank == 48
    strip = {k: ({**v, "self_attn": {n: w for n, w in v["self_attn"].items()
                                     if n != "indexer"}}
                 if k.startswith("layers_") else v)
             for k, v in params.items()}
    assert jax.tree.structure(rd.param_shapes(cfg)) \
        == jax.tree.structure(strip)
    model = rd.RaggedDeepseekV3(cfg, BLOCK)
    assert model.kv_row == {"ckv": 128} and model.index_topk is None
    eng = InferenceEngineV2(model, strip, RaggedInferenceEngineConfig.
                            from_dict({
        "state_manager": {"max_ragged_batch_size": BUDGET,
                          "max_ragged_sequence_count": MAX_SEQS,
                          "max_context": 512},
        "kv_cache": {"block_size": BLOCK, "num_blocks": 120}}))
    eng.PREFILL_TILE = TILE
    assert _gap(_serve(eng, ids, 140), got) <= F32_TOL


@pytest.mark.parametrize("over, match", [
    ({"q_lora_rank": None}, "query latent"),
    ({"index_head_dim": 64}, "128-lane"),
])
def test_what_the_indexer_cannot_be_is_refused_by_name(over, match):
    with pytest.raises(NotImplementedError, match=match):
        family.program_config({**HF, **over})


# ------------------------------------------------------------------ #
# (b) the selection: exactly a stable sort's, ties to the lowest position
# ------------------------------------------------------------------ #
def _sorted_set(scores, k):
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :k]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=1)
    return mask & np.isfinite(scores)


def _tie_scores(seed=0, n=12, c=200):
    """Rows full of ties: few distinct values, zeros of both signs, and
    ``-inf`` past each row's own position."""
    rng = np.random.default_rng(seed)
    s = rng.integers(-3, 4, (n, c)).astype(np.float32)
    s[s == 0] = np.where(rng.random((s == 0).sum()) < 0.5, 0.0, -0.0)
    s[0] = 1.0                                      # one value throughout
    pos = rng.integers(0, c, (n,))
    pos[:3] = (c - 1, 0, 5)
    s[np.arange(c)[None] > pos[:, None]] = -np.inf
    return s, pos


@pytest.mark.parametrize("k", [1, 7, 64, 200, 500])
def test_both_selections_are_a_stable_sorts(k):
    scores, pos = _tie_scores()
    want = _sorted_set(np.where(scores == 0, 0.0, scores), k)
    assert want.sum(1).tolist() == np.minimum(k, pos + 1).tolist()
    s = jnp.where(jnp.asarray(scores) == 0.0, 0.0, jnp.asarray(scores))
    key = sl.sort_key(s)
    thr, cut = jax.jit(sl.select_threshold, static_argnums=1)(key, k)
    place = jnp.arange(scores.shape[1])
    mask = np.asarray(sl.selected(key, place, thr, cut)) \
        & (np.arange(scores.shape[1])[None] <= pos[:, None])
    assert (mask == want).all()
    idx = np.asarray(sl.select_topk(s, k))
    assert idx.shape == (scores.shape[0], min(k, scores.shape[1]))
    got = np.zeros_like(want)
    np.put_along_axis(got, idx, True, axis=1)
    assert ((got & (np.arange(scores.shape[1])[None] <= pos[:, None]))
            == want).all()


def test_the_reference_selects_by_a_stable_sort():
    scores, _ = _tie_scores(seed=1)
    s = np.where(scores == 0, 0.0, scores)
    assert (np.asarray(reference.selection(jnp.asarray(s), 7))
            == _sorted_set(s, 7)).all()


def test_index_scores_walk_a_table_and_stop_at_the_position():
    """Two groups with tables of their own; what lies past a row's position
    (and a pad row's everything) is ``-inf``, the rest is the formula."""
    rng = np.random.default_rng(0)
    g, r, hi, di, b = 2, 3, 4, 128, 6
    pool = jnp.asarray(rng.standard_normal((40 * BLOCK, di)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 40))[:g * b]
                         .reshape(g, b), jnp.int32)
    pos = jnp.asarray([[40, 41, -1], [5, 90, 91]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((g, r, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((g, r, hi)), jnp.float32)
    got = np.asarray(sl.index_scores(q, w, pool, tables, pos,
                                     block_size=BLOCK))
    assert got.shape == (g, r, b * BLOCK)
    rows = (np.asarray(tables)[:, :, None] * BLOCK
            + np.arange(BLOCK)).reshape(g, -1)
    keys = np.asarray(pool)[rows]                          # [G, C, DI]
    want = np.einsum("grjc,grj->grc", np.maximum(
        np.einsum("grjd,gcd->grjc", np.asarray(q), keys), 0), np.asarray(w))
    live = np.arange(b * BLOCK)[None, None] <= np.asarray(pos)[..., None]
    assert np.isneginf(got[~live]).all()
    assert np.allclose(got[live], want[live], rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ #
# (b') the tile rows' read as a Mosaic kernel (interpret mode) against the
# XLA composition on the same operands
# ------------------------------------------------------------------ #
def _tile_operands(dtype, last, scores_of, entries=20, seed=0, rows=TILE,
                   heads=4, width=128, rank=32):
    """A ``masked_latent_read`` call of one tile a sequence: tile ``t``'s
    rows end at position ``last[t]`` (``-1``: a pad tile; rows before
    position 0 are pad rows), its index scores ``scores_of(rng, shape)``,
    its table ``entries`` blocks of a shuffled pool."""
    rng = np.random.default_rng(seed)
    g, c = len(last), entries * BLOCK
    pool = jnp.asarray(rng.standard_normal(((g * entries + 1) * BLOCK,
                                            width)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, g * entries + 1))
                         .reshape(g, entries), jnp.int32)
    pos = np.asarray(last)[:, None] - np.arange(rows)[::-1][None, :]
    pos = np.where((pos >= 0) & (np.asarray(last)[:, None] >= 0), pos, -1)
    scores = np.where(np.arange(c)[None, None] <= pos[..., None],
                      scores_of(rng, (g, rows, c)).astype(np.float32),
                      -np.inf)
    key = sl.sort_key(jnp.asarray(scores))
    thr, cut = sl.select_threshold(key.reshape(g * rows, c), TOPK)
    q = jnp.asarray(0.3 * rng.standard_normal((g, rows, heads, width)),
                    dtype)
    return (q, pool, tables, jnp.asarray(pos, jnp.int32), key,
            thr.reshape(g, rows), cut.reshape(g, rows))


_NORMAL = lambda rng, shape: rng.standard_normal(shape)
#: six values over a context: every row's 24th largest score is a tie
_TIES = lambda rng, shape: rng.integers(0, 6, shape)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 1e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["context_0", "ties", "short_and_long",
                                  "pads"])
def test_the_tile_read_kernel_is_the_masked_read(case, dtype, tol,
                                                 short_key_steps):
    """(a) a chunk at context 0: its first rows hold fewer than
    ``index_topk`` positions and select them all; (b) a context of five key
    steps whose scores tie on every threshold, so ``cut`` decides; (c) the
    end of a long sequence beside the start of a short one: each tile
    stops at its own last position; (d) a pad tile between two live ones, a
    tile with pad rows, and tables whose entries past the context name
    block 0, the allocator's trash block."""
    last, scores_of, trash = {
        "context_0": ([15, 31, 47], _NORMAL, False),
        "ties": ([300, 316], _TIES, False),
        "short_and_long": ([309, 15, 31, 200], _NORMAL, False),
        "pads": ([150, -1, 9, -1], _TIES, True)}[case]
    args = list(_tile_operands(dtype, last, scores_of))
    if trash:       # table entries past each tile's context: block 0
        live = np.asarray(last)[:, None] // BLOCK
        args[2] = jnp.where(np.arange(20)[None] <= live, args[2], 0)
        args[1] = args[1].at[:BLOCK].set(7.0)
    kw = dict(block_size=BLOCK, rank=32, scale=0.2)
    want = np.asarray(sl.masked_latent_read(*args, **kw))
    got = np.asarray(sl.sparse_tile_read(*args, interpret=True, **kw))
    assert got.shape == want.shape and got.dtype == args[0].dtype
    got = got.astype(np.float32)
    assert np.isfinite(got).all()
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))
    # a pad row reads nothing
    assert (got[np.asarray(args[3]) < 0] == 0).all()
    if case == "ties":      # and ``cut`` matters: without it another sum
        args[6] = jnp.full_like(args[6], 1 << 20)
        other = np.asarray(sl.masked_latent_read(*args, **kw))
        assert np.max(np.abs(other - want)) > 1e-2 * np.max(np.abs(want))


def test_the_tile_read_counters_are_the_kernels_rule(short_key_steps):
    """``sparse_tile_key_steps`` counts what the kernel's walk does: steps
    of 64 positions here, a tile up to its own last position."""
    # a chunk of 40 from 100 (tiles 100-115, 116-131, 132-139) beside one of
    # 16 from 0, in a segment of 6 tiles over tables of 32 blocks
    steps, live = sl.sparse_tile_key_steps(
        [(100, 40), (0, 16)], 6, block_size=BLOCK, entries=32, tile_q=TILE)
    assert (steps, live) == (6 * 8, 2 + 3 + 3 + 1)


# ------------------------------------------------------------------ #
# (c) faults: what the check must see (glm_dsa_faults.py, beside this file;
# benchmark/tools/calls/pr50_faults.py applies the same on the chip at the
# published widths)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("interpret", [None, True],
                         ids=["composition", "kernel-interpreted"])
@pytest.mark.parametrize("name, seen", [
    ("indexer_dropped", True), ("recent_topk", True),
    ("k_off_by_block", True), ("indexer_rope_missing", True),
    ("idx_row_fp8", True), ("k_norm_bias_dropped", True),
    # a positive factor on a row's scores changes no order
    ("w_scale_missing", False),
])
def test_a_fault_in_the_indexer_moves_the_logits(name, seen, interpret,
                                                 short_key_steps):
    params = _params()
    ids = _ids(146)
    with fault(name, block=8):
        got = _serve(_engine(params, interpret=interpret), ids, 140)
    gap = _gap(got, _want(params, ids, 140))
    assert (gap > BF16_TOL) if seen else (gap <= F32_TOL), gap


# ------------------------------------------------------------------ #
# (d) both leaves behind one block table
# ------------------------------------------------------------------ #
def test_pool_rows_and_bytes_count_both_leaves():
    eng = _engine(_params())
    kv = eng.state_manager.kv_cache
    assert kv.kv_row == {"ckv": 128, "idx_k": 128}
    assert set(kv.cache["layer_0"]) == {"ckv", "idx_k"}
    assert kv.cache["layer_2"]["idx_k"].shape == (120 * BLOCK, 128)
    assert kv.per_token_bytes == 3 * (128 + 128) * 4       # float32 here
    assert eng.index_topk == TOPK
    # at the published widths: 5 layers x (640 + 128) lanes x 2 B
    real = BlockedKVCache(5, 2, 128, 1, 640, jnp.bfloat16,
                          kv_row={"ckv": 640, "idx_k": 128})
    assert real.per_token_bytes == 5 * 1536 == 7_680


def test_block_copies_gathers_and_scatters_carry_both_leaves():
    params = _params()
    ids = _ids(66, seed=5)
    want = _want(params, ids, 60)
    eng = _engine(params)
    eng.put([1], [ids[:60].tolist()])
    seq = eng.state_manager.get_sequence(1)
    kv = eng.state_manager.kv_cache
    fresh = [100 + i for i in range(len(seq.blocks))]
    for src, dst in zip(seq.blocks, fresh):
        kv.copy_block(src, dst)
    payload = kv.gather_blocks(fresh)
    assert {k: v.shape for k, v in payload["layer_1"].items()} == {
        "ckv": (len(fresh) * BLOCK, 128), "idx_k": (len(fresh) * BLOCK, 128)}
    other = _engine(params)
    other.put([1], [ids[:60].tolist()])
    oseq = other.state_manager.get_sequence(1)
    other.state_manager.kv_cache.update(jax.tree.map(
        jnp.zeros_like, other.state_manager.kv_cache.cache))
    other.state_manager.kv_cache.scatter_blocks(oseq.blocks, payload)
    seq.blocks[:] = fresh
    eng._dev_decode_state = None
    for e in (eng, other):
        assert _gap(_decode(e, ids[60:], 1), want[1:]) <= F32_TOL
    # a payload without the index row is another geometry
    with pytest.raises(ValueError):
        kv.scatter_blocks(fresh, {k: {"ckv": v["ckv"]}
                                  for k, v in payload.items()})


def test_kv_handoff_to_another_engine_carries_both_leaves():
    params = _params()
    ids = _ids(66, seed=11)
    a, b = _engine(params), _engine(params)
    a.put([1], [ids[:60].tolist()])
    snap = a.flush_to_host([1], include_kv=True)[1]
    assert snap["seen_tokens"] == 60 \
        and set(snap["kv"]["layer_0"]) == {"ckv", "idx_k"}
    assert b.resume(9, ids[:60].tolist(), kv_state=snap) == {}
    assert _gap(_decode(b, ids[60:], 9),
                _want(params, ids, 60)[1:]) <= F32_TOL


def test_host_tier_spools_and_restores_both_leaves():
    params = _params()
    eng = _engine(params, blocks=8, enable_prefix_cache=True, host_tier=True,
                  host_tier_bytes=1 << 22)
    a, b = _ids(60, seed=12), _ids(90, seed=13)
    eng.put([1], [a.tolist()])
    eng.flush([1])
    eng.put([2], [b.tolist()])
    eng.flush([2])
    ids = np.concatenate([a[:50], _ids(6, seed=14)])
    got = _serve(eng, ids, 50, uid=3)
    assert eng.prefix_cache_stats.hit_tokens >= 48
    assert _gap(got, _want(params, ids, 50)) <= F32_TOL


def test_prefix_cache_attach_and_fork_serve_both_leaves():
    """The second request shares the first one's prefix: its warm blocks
    are attached (latent rows AND index keys: the attached positions are
    scored without having been prefilled here), the rest forks."""
    params = _params()
    eng = _engine(params, enable_prefix_cache=True)
    a = _ids(60, seed=6)
    b = np.concatenate([a[:40], _ids(26, seed=7)])
    eng.put([1], [a.tolist()])
    got = _serve(eng, b, 60, uid=2)
    assert eng.prefix_cache_stats.hit_tokens == 32   # two whole blocks
    assert _gap(got, _want(params, b, 60)) <= F32_TOL


def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


def _solo(eng, prompt, n_new):
    sched = ContinuousBatchScheduler(eng)
    req = sched.submit(list(prompt), _greedy(n_new))
    sched.run_until_idle()
    return list(req.generated)


def test_a_preemption_by_recompute_rebuilds_both_leaves():
    """Six requests whose decodes outgrow a pool of 15 blocks: the newest
    is preempted and recomputed; every request ends with the tokens of its
    own undisturbed run."""
    params = _params()
    prompts = [_ids(20 + 7 * i, seed=40 + i).tolist() for i in range(6)]
    news = [30 + (i % 3) for i in range(6)]
    eng = _engine(params, blocks=16)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts, news)]
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    alone = _engine(params, max_seqs=4)
    assert [list(r.generated) for r in reqs] == \
        [_solo(alone, p, n) for p, n in zip(prompts, news)]
    assert eng.state_manager.free_blocks == 15


@pytest.mark.parametrize("path", ["int8", "verify_step"])
def test_what_two_leaves_cannot_do_refuses_by_name(path):
    params = _params()
    if path == "int8":
        with pytest.raises((ValueError, NotImplementedError), match="int8"):
            _engine(params, dtype="int8")
        return
    eng = _engine(params)
    eng.put([1], [_ids(20).tolist()])
    with pytest.raises(NotImplementedError, match="latent row"):
        eng.verify_step([1], [[3, 4]])


# ------------------------------------------------------------------ #
# (e) counters and device scopes
# ------------------------------------------------------------------ #
def test_the_four_counters_match_a_hand_count(short_key_steps):
    """100 tokens: chunks of 64 and 36 at a 64-token budget; then a join of
    40 tokens beside the first one's decode; then two prompts at once, the
    end of the first beside the start of the second in one batch."""
    tracer = Tracer()
    eng = _engine(_params())
    sched = ContinuousBatchScheduler(eng, tracer=tracer)
    first = sched.submit(_ids(100).tolist(), _greedy(8))
    for _ in range(3):
        sched.step()
    sched.submit(_ids(40, seed=4).tolist(), _greedy(3))
    sched.run_until_idle()
    assert len(first.generated) == 8
    spans = [r for r in tracer.records() if r.get("ph") == "X"]
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    k = TOPK
    tri = lambda a, n: n * (2 * a + n + 1) // 2      # sum of p + 1
    assert [(a["idx_keys"], a["sel_keys"], a.get("idx_pairs"),
             a.get("sel_pairs")) for a in built] == [
        # positions 0-63: the first 24 rows see all p + 1, the rest 24
        (0, 0, tri(0, 64), tri(0, k) + (64 - k) * k),
        # 64-99 over 100 rows: every row past index_topk
        (0, 0, tri(64, 36), 36 * k),
        # the join of 40 beside a one-token row feeding position 102 (the
        # prompt's 100, the decode step consumed and the one sent ahead)
        (103, k, tri(0, 40), tri(0, k) + (40 - k) * k)]
    assert all("latent_key_steps" not in a for a in built)
    # the tile rows' walk, in steps of 64 positions over tables of 32
    # blocks (8 steps a tile): a tile walks to its own last position
    assert [(a["bucket"], a["sparse_key_steps"],
             a["sparse_live_key_steps"]) for a in built] == [
        (MAX_SEQS + 64, 4 * 8, 1 + 1 + 1 + 1),      # rows end at 15 .. 63
        (MAX_SEQS + 64, 4 * 8, 2 + 2 + 2),          # at 79, 95, 99
        (MAX_SEQS + 64, 4 * 8, 1 + 1 + 1)]          # at 15, 31, 39
    dec = [r["attrs"] for r in spans if r["name"] == "decode"]
    # every decoding row is past index_topk: each reads 24 of its p + 1
    assert dec and all(a["sel_keys"] in (k, 2 * k)
                       and a["idx_keys"] > a["sel_keys"] for a in dec)
    # the first consumed step feeds position 100 alone
    assert (dec[0]["idx_keys"], dec[0]["sel_keys"]) == (101, k)
    # rows of both requests in one step: each scored p + 1, read 24
    assert any(a["sel_keys"] == 2 * k for a in dec)
    # two chunks in one batch: 70 tokens and 20; the second batch holds the
    # first prompt's last 6 (one tile, rows end at 69: two steps) beside
    # the second's 20 (tiles that end at 15 and 19: one step each)
    sched.submit(_ids(70, seed=5).tolist(), _greedy(2))
    sched.submit(_ids(20, seed=6).tolist(), _greedy(2))
    sched.run_until_idle()
    built = [r["attrs"] for r in tracer.records()
             if r.get("ph") == "X" and r["name"] == "engine/build_batch"
             and r["attrs"].get("chunk_seqs")][3:]
    assert [(a["chunk_seqs"], a["chunk_tokens"], a["sparse_key_steps"],
             a["sparse_live_key_steps"]) for a in built] == [
        (1, 64, 4 * 8, 4), (2, 26, 4 * 8, 2 + 1 + 1)]
    assert all(a["sparse_live_key_steps"] < a["sparse_key_steps"]
               for a in built)


def test_device_scopes_of_the_indexer_and_the_sparse_read():
    eng = _engine(_params(), max_seqs=4)
    eng.put([1], [_ids(70).tolist()])
    eng.decode_step([1], [3])
    text = eng.lower_step(("decode_step",)).as_text(debug_info=True)
    for scope in ("layers_0/attn/q_proj", "layers_0/attn/kv_latent",
                  "layers_0/attn/index_k", "layers_1/attn/index_score",
                  "layers_1/attn/index_topk", "layers_2/attn/sparse_read",
                  "layers_2/attn/out_proj", "layers_0/mlp",
                  "layers_1/moe/router", "layers_2/moe/experts", "lm_head"):
        assert f'"jit(decode_step)/{scope}' in text, scope
    assert "attn/latent_read" not in text
    tiled = [k for k in eng.step_keys if k != ("decode_step",) and k[0] > 4]
    text = eng.lower_step(tiled[0]).as_text(debug_info=True)
    for scope in ("attn/index_k", "attn/index_score", "attn/index_topk",
                  "attn/sparse_read"):
        assert f"layers_1/{scope}" in text, scope
    assert "attn/prefill_read" not in text and "attn/expand" not in text
    assert "_sparse_tile_read_kernel" not in text       # the composition
    # through the kernel: the jitted wrapper is lowered once and called
    # under the read's scope in every layer, the ``pallas_call`` inside it
    # by its own name (the compiled op's name is the two joined)
    eng = _engine(_params(), max_seqs=4, interpret=True)
    eng._get_step(4 + TILE, TILE)       # built, not run: one tile
    text = eng.lower_step((4 + TILE, TILE)).as_text(debug_info=True)
    for i in range(HF["num_hidden_layers"]):
        assert (f'/layers_{i}/attn/sparse_read/jit(sparse_tile_read)"'
                in text), i
    assert 'loc("_sparse_tile_read_kernel/pallas_call"' in text


# ------------------------------------------------------------------ #
# (f) the share: 16 chips' experts add up to the uncut layer
# ------------------------------------------------------------------ #
def test_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """64 experts at top-8 in 16 shares of 4 (the deployment's split at a
    quarter of its count): the shares' routed parts plus the ONE shared
    expert counted once equal the uncut reference layer."""
    from benchmark.reference import moonlight as moe_reference

    rng = np.random.default_rng(4)
    h, f, e, k, t = 64, 32, 64, 8, 50
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x = f32(t, h)
    router = 2.0 * f32(h, e) * h ** -0.5
    bias = 0.3 * f32(e)
    w_gate, w_up = f32(e, h, f) * h ** -0.5, f32(e, h, f) * h ** -0.5
    w_down = f32(e, f, h) * f ** -0.5
    shared = {"shared_expert": {
        "gate_proj": {"kernel": f32(h, f) * h ** -0.5},
        "up_proj": {"kernel": f32(h, f) * h ** -0.5},
        "down_proj": {"kernel": f32(f, h) * f ** -0.5}}}
    gate = {"wg": {"kernel": router}, "e_score_correction_bias": bias}

    def share(start, count, with_shared):
        moe = {"gate": gate,
               "experts": {"w_gate": w_gate[start:start + count],
                           "w_up": w_up[start:start + count],
                           "w_down": w_down[start:start + count]},
               **(shared if with_shared else {})}
        return np.asarray(dropless_moe(x, moe, k, jnp.float32,
                                       expert_start=start,
                                       routed_scale=2.5))

    parts = [share(4 * s, 4, with_shared=(s == 0)) for s in range(16)]
    se = shared["shared_expert"]
    lp = {"router": router, "bias": bias, "w_gate": w_gate, "w_up": w_up,
          "w_down": w_down, "s_gate": se["gate_proj"]["kernel"],
          "s_up": se["up_proj"]["kernel"],
          "s_down": se["down_proj"]["kernel"]}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(
            moe_reference.routed(x, lp, top_k=k, norm_topk=True, scale=2.5,
                                 expert_start=0)
            + moe_reference.shared(x, lp))
    assert np.max(np.abs(sum(parts) - want)) <= 1e-5 * np.max(np.abs(want))
    # a share alone is a part, not the whole
    assert np.max(np.abs(parts[1] - want)) > 0.1 * np.max(np.abs(want))
    # the shared expert counted sixteen times is not the layer
    twice = sum(share(4 * s, 4, with_shared=True) for s in range(16))
    assert np.max(np.abs(twice - want)) > 0.1 * np.max(np.abs(want))


# ------------------------------------------------------------------ #
# (g) the loader: the published names, both rope layouts de-interleaved
# ------------------------------------------------------------------ #
def _interleave(kernel, width, rope, first):
    """A rotate-half kernel [in, out] -> the published [out, in] weight
    whose rotary dims (the first or last ``rope`` of every ``width``
    outputs) are interleaved: the inverse of what the loader does."""
    w = np.asarray(kernel).T
    at = 0 if first else width - rope
    order = np.arange(width)
    half = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    order[at + half] = at + np.arange(rope)
    blocks = w.reshape(-1, width, w.shape[-1])[:, order]
    return blocks.reshape(w.shape)


def test_loader_on_a_synthetic_glm_moe_dsa_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, ``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj``,
    ``indexer.{wq_b, wk, k_norm, weights_proj}``, both rope layouts
    interleaved, one ``experts.<e>`` module an expert, and a
    multi-token-prediction layer after the model's own): the loaded tree is
    the model's, and the engine serves the reference's logits.  (No GLM-5
    checkpoint is in the repository.)"""
    import json

    from safetensors.numpy import save_file

    from deepspeed_tpu.checkpoint.hf_loader import (config_from_hf,
                                                    load_hf_checkpoint)

    hf = {**HF, "n_routed_experts": 8, "rope_interleave": True,
          "indexer_rope_interleave": True, "num_nextn_predict_layers": 1}
    hf.pop("router_experts"), hf.pop("expert_start")
    p = _params(hf, seed=4)
    tensors = {}
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]

    def put(name, a):
        tensors[name] = np.ascontiguousarray(np.asarray(a, np.float32))

    put("model.embed_tokens.weight", p["embed_tokens"]["embedding"])
    put("model.norm.weight", p["norm"]["scale"])
    put("lm_head.weight", p["lm_head"]["kernel"].T)
    n = hf["num_hidden_layers"]
    for i in range(n + 1):              # the last one: the MTP layer
        lp, pre = p[f"layers_{min(i, n - 1)}"], f"model.layers.{i}."
        for norm in ("input_layernorm", "post_attention_layernorm"):
            put(pre + norm + ".weight", lp[norm]["scale"])
        att, ix = lp["self_attn"], lp["self_attn"]["indexer"]
        put(pre + "self_attn.q_a_proj.weight", att["q_a_proj"]["kernel"].T)
        put(pre + "self_attn.q_a_layernorm.weight",
            att["q_a_layernorm"]["scale"])
        put(pre + "self_attn.q_b_proj.weight", _interleave(
            att["q_b_proj"]["kernel"], nope + rope, rope, first=False))
        put(pre + "self_attn.kv_a_proj_with_mqa.weight", _interleave(
            att["kv_a_proj_with_mqa"]["kernel"],
            hf["kv_lora_rank"] + rope, rope, first=False))
        put(pre + "self_attn.kv_a_layernorm.weight",
            att["kv_a_layernorm"]["scale"])
        put(pre + "self_attn.kv_b_proj.weight", att["kv_b_proj"]["kernel"].T)
        put(pre + "self_attn.o_proj.weight", att["o_proj"]["kernel"].T)
        put(pre + "self_attn.indexer.wq_b.weight", _interleave(
            ix["wq_b"]["kernel"], hf["index_head_dim"], rope, first=True))
        put(pre + "self_attn.indexer.wk.weight", _interleave(
            ix["wk"]["kernel"], hf["index_head_dim"], rope, first=True))
        put(pre + "self_attn.indexer.k_norm.weight", ix["k_norm"]["scale"])
        put(pre + "self_attn.indexer.k_norm.bias", ix["k_norm"]["bias"])
        put(pre + "self_attn.indexer.weights_proj.weight",
            ix["weights_proj"]["kernel"].T)
        mlp = lp["mlp"]
        if i == n:
            put(pre + "enorm.weight", lp["input_layernorm"]["scale"])
            put(pre + "hnorm.weight", lp["input_layernorm"]["scale"])
            put(pre + "eh_proj.weight", np.zeros((64, 128)))
            put(pre + "shared_head.norm.weight", p["norm"]["scale"])
        if "gate" not in mlp:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                put(f"{pre}mlp.{proj}.weight", mlp[proj]["kernel"].T)
            continue
        put(pre + "mlp.gate.weight", mlp["gate"]["wg"]["kernel"].T)
        put(pre + "mlp.gate.e_score_correction_bias",
            mlp["gate"]["e_score_correction_bias"])
        for proj in ("gate_proj", "up_proj", "down_proj"):
            put(f"{pre}mlp.shared_experts.{proj}.weight",
                mlp["shared_expert"][proj]["kernel"].T)
        for e in range(8):
            for proj, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                               ("down_proj", "w_down")):
                put(f"{pre}mlp.experts.{e}.{proj}.weight",
                    mlp["experts"][leaf][e].T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(
        {**hf, "architectures": ["GlmMoeDsaForCausalLM"]}))

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "glm_moe_dsa" and cfg.rope_theta == 1e6
    assert (cfg.q_lora_rank, cfg.index_topk, cfg.index_n_heads,
            cfg.index_head_dim) == (48, TOPK, 4, 128)
    loaded = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert set(flat_w) == set(flat_g)           # the MTP layer is dropped
    for path, a in flat_w.items():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(flat_g[path]),
                                      err_msg=str(path))
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), dtype=jnp.float32, config=_engine(p, hf=hf).config)
    eng.PREFILL_TILE = TILE
    ids = _ids(66)
    got = _serve(eng, ids, 60)
    ref = family.reference_params(loaded)
    # the loader applies no seeded-bias mapping: the reference reads the
    # bias as the checkpoint has it
    for i, layer in enumerate(ref["layers"]):
        if "bias" in layer:
            layer["bias"] = loaded[f"layers_{i}"]["mlp"]["gate"][
                "e_score_correction_bias"]
    # (the reference refuses a configuration with the MTP module by name)
    with pytest.raises(ValueError, match="MTP"):
        reference.logits_at(ref, ids, hf, rows=[59])
    want = reference.logits_at(ref, ids, {**hf, "num_nextn_predict_layers": 0},
                               rows=list(range(59, 66)))
    assert _gap(got, want) <= F32_TOL
    # a checkpoint that says its rope dims are NOT interleaved is read as
    # it lies
    from deepspeed_tpu.checkpoint import hf_loader
    tf = hf_loader._deepseek_v3_rope_rows(lambda c: 6, first=True,
                                          flag="indexer_rope_interleave")
    w = np.repeat(np.arange(6.0)[:, None], 2, axis=1)
    plain = {"qk_rope_head_dim": 4, "indexer_rope_interleave": False}
    assert tf(w, plain)[0].tolist() == [0, 1, 2, 3, 4, 5]
    assert tf(w, {"qk_rope_head_dim": 4})[0].tolist() == [0, 2, 1, 3, 4, 5]
