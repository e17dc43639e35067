"""Moonlight (``model_type: deepseek_v3``) through the normal serving path at
a small size on the CPU: ``RaggedDeepseekV3`` -> ``InferenceEngineV2``
(``put``, ``decode_step``, two-segment batches, a latent pool) ->
``ContinuousBatchScheduler``, against the benchmark's plain float32
reference (``benchmark/reference/moonlight.py``: the expanded form, no
cache; there is one copy, the benchmark's).

What makes the model what it is is drawn away from its neutral value so that
leaving it out fails: norm weights uniform in 0.5 .. 1.5 (the latent norm's
too), a selection bias of N(0, 0.3^2) on a router of N(0, 4/H), a share of 4
of 8 experts from id 2, two shared experts, a leading dense layer.
"""

import functools
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

from benchmark.families import moonlight as family          # noqa: E402
from benchmark.reference import moonlight as reference      # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import latent_flash as lf  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_deepseek_v3 as rd)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe  # noqa: E402
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.ops.grouped_gemm import sigmoid_bias_topk_routing  # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)

# the published keys at the test's size: what the reference and the family
# adapter read
HF = {"model_type": "deepseek_v3", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "moe_intermediate_size": 32,
      "num_hidden_layers": 3, "num_attention_heads": 4,
      "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
      "router_experts": 8, "expert_start": 2, "n_shared_experts": 2,
      "num_experts_per_tok": 2, "first_k_dense_replace": 1,
      "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
      "norm_topk_prob": True, "routed_scaling_factor": 2.446,
      "scoring_func": "sigmoid", "topk_method": "noaux_tc",
      "rope_theta": 50000, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 512}
# widths the Mosaic kernels can tile (interpret mode runs them here)
HF_KERNEL = dict(HF, num_attention_heads=2, kv_lora_rank=128,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
MAX_SEQS, BUDGET, TILE, BLOCK = 40, 64, 16, 16

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same float32 mathematics in another
# order (the absorbed form; a cache; flat ragged rows), measured 6e-7 ..
# 1e-6 here.  1e-4 is ~100x that and far below what a lost chunk, a wrong
# row or a dropped bias moves the logits by (0.05 or more).
F32_TOL = 1e-4
# bf16 engine against the float32 reference on the same bf16-rounded
# weights: bf16 activation roundings and the routings they flip.  Measured
# here over four seeds: 0.007 .. 0.019.  0.03 is the benchmark's own limit
# (``LOGIT_TOL`` of ``runners/serve_ragged.py``).
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


def _ref_params(params):
    """The reference's dict of the program's own values (the family's
    seeded-bias mapping is the benchmark's, undone here)."""
    ref = family.reference_params(params)
    for lp in ref["layers"]:
        if "bias" in lp:
            lp["bias"] = (lp["bias"] - family.BIAS_MEAN) / family.BIAS_STD
    return ref


def _engine(params, act=jnp.float32, hf=HF, interpret=None, blocks=120,
            max_context=512, max_seqs=MAX_SEQS, **kv):
    model = rd.RaggedDeepseekV3(_config(act, hf), BLOCK)
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(act), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks, **kv}}))
    eng.PREFILL_TILE = TILE          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt, uid=7):
    got = [np.asarray(eng.put([uid], [ids[:n_prompt].tolist()])[uid],
                      np.float32)]
    for t in ids[n_prompt:]:
        row = eng.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(params, ids, n_prompt, hf=HF):
    return reference.logits_at(_ref_params(params), ids, hf,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ------------------------------------------------------------------ #
# (a) one prompt in 1, 2 and 5 chunks, then 6 decode steps: the expanded
# path for the chunks, the absorbed one for the decoded tokens, and the
# row between them
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, hf, interpret", [
    (50, HF, None), (100, HF, None), (300, HF, None),
    (100, HF_KERNEL, True)],
    ids=["1chunk", "2chunks", "5chunks", "2chunks-kernels-interpreted"])
def test_f32_engine_matches_reference(n_prompt, hf, interpret):
    params = _params(hf)
    ids = _ids(n_prompt + 6)
    got = _serve(_engine(params, hf=hf, interpret=interpret), ids, n_prompt)
    assert _gap(got, _want(params, ids, n_prompt, hf)) <= F32_TOL


def test_absorbed_and_expanded_compositions_agree():
    """Every row of a two-segment batch through both XLA forms."""
    rng = np.random.default_rng(8)
    h, rank, nope, rope, vd, bs, b = 4, 32, 16, 8, 16, 16, 4
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f32(9 * bs, 128).at[:, rank + rope:].set(0)
    w_kvb = f32(rank, h * (nope + vd)) * rank ** -0.5
    tables = jnp.asarray([[3, 1, 7, 0], [2, 5, 0, 0]], jnp.int32)
    slot = jnp.asarray([0, 1, 0, 1, 1], jnp.int32)
    pos = jnp.asarray([40, 17, 3, -1, 31], jnp.int32)
    q_nope, q_pe = f32(5, h, nope), f32(5, h, rope)
    scale = (nope + rope) ** -0.5
    want = rd.expanded_read_xla(q_nope, q_pe, pool, w_kvb, tables, slot,
                                pos, bs, rank, scale)
    w3 = w_kvb.reshape(rank, h, nope + vd)
    q_cat = jnp.concatenate(
        [jnp.einsum("thd,chd->thc", q_nope, w3[..., :nope]), q_pe,
         jnp.zeros((5, h, 128 - rank - rope))], -1)
    o_lat = rd.absorbed_read_xla(q_cat, pool, tables, slot, pos, bs, rank,
                                 scale)
    got = jnp.einsum("thc,chd->thd", o_lat, w3[..., nope:])
    real = np.asarray(pos) >= 0
    assert np.max(np.abs(np.asarray(got - want)[real])) <= 1e-5
    assert np.isfinite(np.asarray(got)).all()       # the pad row too


def test_bf16_engine_is_the_same_model():
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), _params())
    ids = _ids(106)
    got = _serve(_engine(params, act=jnp.bfloat16), ids, 100)
    assert _gap(got, _want(params, ids, 100)) <= BF16_TOL


def test_a_chunk_that_forgets_the_cached_context_fails_the_tolerance(
        monkeypatch):
    """The second chunk reading only what this step wrote."""
    real = rd.expanded_read_xla

    def forgetful(q_nope, q_pe, pool, w_kvb, tables, slot, pos, *rest):
        first = jnp.min(jnp.where(pos >= 0, pos, 1 << 30))
        rows = jnp.arange(pool.shape[0])
        held = tables[slot[0]]
        row_pos = jnp.argmax(held[:, None] == (rows // BLOCK)[None, :],
                             axis=0) * BLOCK + rows % BLOCK
        return real(q_nope, q_pe,
                    jnp.where((row_pos >= first)[:, None], pool, 0), w_kvb,
                    tables, slot, pos, *rest)

    monkeypatch.setattr(rd, "expanded_read_xla", forgetful)
    params = _params()
    ids = _ids(106)
    got = _serve(_engine(params), ids, 100)
    assert _gap(got, _want(params, ids, 100)) > 300 * F32_TOL


# ------------------------------------------------------------------ #
# (b) sequences interleaved through the scheduler at more than 32 slots
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


def _solo(eng, prompt, n_new):
    """One request served alone (``eng``: an engine of its own, reused
    from one solo run to the next so its programs compile once)."""
    sched = ContinuousBatchScheduler(eng)
    req = sched.submit(list(prompt), _greedy(n_new))
    sched.run_until_idle()
    return list(req.generated)


PROMPT_LENS, NEW = (150, 40, 90, 7), (4, 9, 5, 12)


@pytest.fixture(scope="module")
def runs():
    params = _params()
    prompts = [_ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    return params, prompts


def test_interleaved_logits_match_each_reference(runs):
    """Four requests of different lengths join one after another at 40
    slots: the longer ones' chunks share batches with the others' decodes."""
    from interleaved_logits import serve_and_compare

    params, prompts = runs
    eng = _engine(params)
    out = serve_and_compare(eng, reference, _ref_params(params),
                            HF, prompts, NEW)
    assert max(out["gaps"]) <= F32_TOL, out
    assert eng._batch.max_seqs == 40 > 32
    assert eng.state_manager.free_blocks == 119      # every block returned


def test_many_slots_a_flush_and_a_preemption_by_recompute(runs):
    """36 requests in flight at once (more than 32 rows of one decode
    step), one flushed mid-flight and served again, on a pool the decodes
    outgrow: the newest is preempted and recomputed; every request ends
    with the tokens of its own undisturbed run."""
    params, prompts = runs
    many = [_ids(5 + (i % 7), seed=40 + i).tolist() for i in range(36)]
    news = [14 + (i % 5) for i in range(36)]
    eng = _engine(params, blocks=44)       # 43 usable blocks of 16 tokens
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(many, news)]
    most = 0
    for _ in range(12):                    # four prompts join a tick
        sched.step()
        most = max(most, len(sched.running_decode_uids))
    assert most > 32
    long = sched.submit(prompts[0], _greedy(30))
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    alone = _engine(params, max_seqs=4)
    assert [list(r.generated) for r in reqs] == \
        [_solo(alone, p, n) for p, n in zip(many, news)]
    assert list(long.generated) == _solo(alone, prompts[0], 30)
    # a flush gives the blocks back; the same uid served again is new
    ids = _ids(46, seed=77)
    first = _serve(eng, ids, 40, uid=5)
    assert eng.state_manager.free_blocks == 43
    assert np.array_equal(first, _serve(eng, ids, 40, uid=5))


# ------------------------------------------------------------------ #
# (c) the router
# ------------------------------------------------------------------ #
def test_router_selects_by_score_plus_bias_and_weighs_by_score():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.0, 0.0, 3.0, -3.0]], jnp.float32)
    bias = jnp.asarray([0.0, -0.5, 0.0, 0.6], jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits)))
    # row 0: s = .881 .731 .5 .269; s + b = .881 .231 .5 .869 -> {0, 3}
    # (without the bias: {0, 1}); row 1: .5 .5 .953 .047 -> + b: .5 0 .953
    # .647 -> {2, 3} (without: {2, 0})
    topi, topw = sigmoid_bias_topk_routing(logits, bias, 2, True, 2.446)
    assert np.asarray(topi).tolist() == [[0, 3], [2, 3]]
    want = np.stack([s[0, [0, 3]] / s[0, [0, 3]].sum(),
                     s[1, [2, 3]] / s[1, [2, 3]].sum()]) * 2.446
    assert np.allclose(np.asarray(topw), want, rtol=1e-6)
    plain_i, plain_w = sigmoid_bias_topk_routing(logits, 0 * bias, 2, False)
    assert np.asarray(plain_i).tolist() == [[0, 1], [2, 0]]
    assert np.allclose(np.asarray(plain_w), [s[0, [0, 1]], s[1, [2, 0]]])
    # the reference's router is the same function of the same inputs
    ref_i, ref_w = reference.route(logits, jnp.eye(4), bias, 2, True, 2.446)
    assert np.array_equal(np.asarray(ref_i), np.asarray(topi))
    assert np.allclose(np.asarray(ref_w), np.asarray(topw), rtol=1e-6)


@pytest.mark.parametrize("over, match", [
    ({"n_group": 2}, "n_group=2"), ({"topk_group": 2}, "topk_group=2"),
    ({"topk_method": "greedy"}, "topk_method='greedy'"),
    ({"scoring_func": "softmax"}, "scoring_func='softmax'")])
def test_what_the_router_does_not_compute_is_refused_by_name(over, match):
    with pytest.raises(NotImplementedError, match=match):
        family.program_config(dict(HF, **over))
    if "n_group" in over or "topk_group" in over:
        with pytest.raises(ValueError, match="n_group"):
            reference.logits_at({}, _ids(4), dict(HF, **over), rows=[0])


def test_a_low_rank_query_and_an_indexer_are_read_from_published_keys():
    """``q_lora_rank`` and the three ``index_*`` keys change the parameter
    tree and the pool row of the configurations that set them; Moonlight's
    (``q_lora_rank`` None, no ``index_topk``) keeps its tree, its one-leaf
    row and its scopes."""
    plain = rd.RaggedDeepseekV3(_config(jnp.float32), BLOCK)
    assert plain.kv_row == {"ckv": 128} and plain.index_topk is None
    att = rd.param_shapes(plain.config)["layers_1"]["self_attn"]
    assert set(att) == {"q_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
                        "kv_b_proj", "o_proj"}
    low = rd.DeepseekV3Config(**{**vars(plain.config), "q_lora_rank": 48})
    att = rd.param_shapes(low)["layers_1"]["self_attn"]
    assert "q_proj" not in att and att["q_a_proj"]["kernel"].shape == (64, 48)
    assert att["q_a_layernorm"]["scale"].shape == (48,)
    assert att["q_b_proj"]["kernel"].shape == (48, 4 * 24)
    assert "indexer" not in att
    assert rd.RaggedDeepseekV3(low, BLOCK).kv_row == {"ckv": 128}
    dsa = rd.DeepseekV3Config(**{**vars(low), "index_topk": 24,
                                 "index_n_heads": 4})
    ix = rd.param_shapes(dsa)["layers_0"]["self_attn"]["indexer"]
    assert {k: tuple(v[n].shape for n in sorted(v)) for k, v in ix.items()} \
        == {"wq_b": ((48, 512),), "wk": ((64, 128),),
            "k_norm": ((128,), (128,)), "weights_proj": ((64, 4),)}
    model = rd.RaggedDeepseekV3(dsa, BLOCK)
    assert model.kv_row == {"ckv": 128, "idx_k": 128}
    assert model.index_topk == 24
    with pytest.raises(NotImplementedError, match="query latent"):
        rd.DeepseekV3Config(**{**vars(plain.config), "index_topk": 24})


def test_moonlights_programs_keep_their_reads():
    """No indexer, no ``attn/index_*`` or ``attn/sparse_read`` scope: the
    dense latent reads, as before."""
    eng = _engine(_params(), max_seqs=4)
    eng.put([1], [_ids(70).tolist()])
    eng.decode_step([1], [3])
    for key in eng.step_keys:
        text = eng.lower_step(key).as_text(debug_info=True)
        assert "attn/index_" not in text and "attn/sparse_read" not in text
        assert "attn/latent_read" in text or "attn/prefill_read" in text
    assert eng.index_topk is None


# ------------------------------------------------------------------ #
# (d) the share test: the shares' parts add up to the uncut layer
# ------------------------------------------------------------------ #
def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts at top-4 in 4 shares (expert_start 0, 4, 8, 12): the four
    shares' routed parts plus the shared experts counted once equal the
    uncut reference layer."""
    rng = np.random.default_rng(4)
    h, f, e, k, t = 64, 32, 16, 4, 50
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x = f32(t, h)
    router = 2.0 * f32(h, e) * h ** -0.5
    bias = 0.3 * f32(e)
    w_gate, w_up = f32(e, h, f) * h ** -0.5, f32(e, h, f) * h ** -0.5
    w_down = f32(e, f, h) * f ** -0.5
    shared = {"shared_expert": {
        "gate_proj": {"kernel": f32(h, 2 * f) * h ** -0.5},
        "up_proj": {"kernel": f32(h, 2 * f) * h ** -0.5},
        "down_proj": {"kernel": f32(2 * f, h) * (2 * f) ** -0.5}}}
    gate = {"wg": {"kernel": router}, "e_score_correction_bias": bias}

    def share(start, count, with_shared):
        moe = {"gate": gate,
               "experts": {"w_gate": w_gate[start:start + count],
                           "w_up": w_up[start:start + count],
                           "w_down": w_down[start:start + count]},
               **(shared if with_shared else {})}
        return np.asarray(dropless_moe(x, moe, k, jnp.float32,
                                       expert_start=start,
                                       routed_scale=2.446))

    parts = [share(4 * s, 4, with_shared=(s == 0)) for s in range(4)]
    se = shared["shared_expert"]
    lp = {"router": router, "bias": bias, "w_gate": w_gate, "w_up": w_up,
          "w_down": w_down, "s_gate": se["gate_proj"]["kernel"],
          "s_up": se["up_proj"]["kernel"],
          "s_down": se["down_proj"]["kernel"]}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(
            reference.routed(x, lp, top_k=k, norm_topk=True, scale=2.446,
                             expert_start=0) + reference.shared(x, lp))
    assert np.max(np.abs(sum(parts) - want)) <= 1e-5 * np.max(np.abs(want))
    # a share alone is a part, not the whole: most rows are routed elsewhere
    assert np.max(np.abs(parts[1] - want)) > 0.1 * np.max(np.abs(want))
    # every expert held: the path OLMoE takes, no share logic
    whole = np.asarray(dropless_moe(
        x, {"gate": gate,
            "experts": {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            **shared}, k, jnp.float32, routed_scale=2.446))
    assert np.max(np.abs(whole - want)) <= 1e-5 * np.max(np.abs(want))
    # the bias matters here: dropping it moves the layer's output
    plain = np.asarray(dropless_moe(
        x, {"gate": dict(gate, e_score_correction_bias=0 * bias),
            "experts": {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            **shared}, k, jnp.float32, routed_scale=2.446))
    assert np.max(np.abs(plain - want)) > 0.05 * np.max(np.abs(want))


# ------------------------------------------------------------------ #
# (e) block operations on a latent pool; what a latent row cannot do
# ------------------------------------------------------------------ #
def test_latent_pool_rows_and_bytes():
    eng = _engine(_params())
    kv = eng.state_manager.kv_cache
    assert kv.kv_row == {"ckv": 128}                 # 32 + 8 -> one tile
    assert set(kv.cache["layer_0"]) == {"ckv"}
    assert kv.cache["layer_2"]["ckv"].shape == (120 * BLOCK, 128)
    assert kv.per_token_bytes == 3 * 128 * 4         # float32 here
    # at the published widths: 13 layers x 640 lanes x 2 B
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache

    real = BlockedKVCache(13, 2, 128, 1, 640, jnp.bfloat16,
                          kv_row={"ckv": lf.latent_row_width(512, 64)})
    assert real.per_token_bytes == 13 * 1280 == 16_640


def test_block_copies_gathers_and_scatters_carry_a_latent_row():
    params = _params()
    ids = _ids(46, seed=5)
    want = _want(params, ids, 40)
    eng = _engine(params)
    eng.put([1], [ids[:40].tolist()])
    seq = eng.state_manager.get_sequence(1)
    kv = eng.state_manager.kv_cache
    # copy every block of the sequence elsewhere and point the table there
    fresh = [100 + i for i in range(len(seq.blocks))]
    for src, dst in zip(seq.blocks, fresh):
        kv.copy_block(src, dst)
    payload = kv.gather_blocks(fresh)
    assert payload["layer_1"]["ckv"].shape == (len(fresh) * BLOCK, 128)
    # ... and a second engine takes the payload through scatter_blocks
    other = _engine(params)
    other.put([1], [ids[:40].tolist()])
    oseq = other.state_manager.get_sequence(1)
    other.state_manager.kv_cache.update(jax.tree.map(
        jnp.zeros_like, other.state_manager.kv_cache.cache))
    other.state_manager.kv_cache.scatter_blocks(oseq.blocks, payload)
    seq.blocks[:] = fresh
    eng._dev_decode_state = None
    for e in (eng, other):
        got = [np.asarray(jax.device_get(e.decode_step([1], [int(t)])),
                          np.float32)[0] for t in ids[40:]]
        assert _gap(np.stack(got), want[1:]) <= F32_TOL
    with pytest.raises(ValueError, match="cache geometry differs"):
        kv.scatter_blocks(fresh, {k: {"ckv": v["ckv"][:, :64]}
                                  for k, v in payload.items()})


def test_kv_handoff_to_another_engine_carries_the_latent_rows():
    """``flush_to_host(include_kv=True)`` on one engine, ``resume`` with
    the payload on another (the disaggregated prefill -> decode handoff):
    no recompute, and the decoded logits are the reference's."""
    params = _params()
    ids = _ids(46, seed=11)
    a, b = _engine(params), _engine(params)
    a.put([1], [ids[:40].tolist()])
    snap = a.flush_to_host([1], include_kv=True)[1]
    assert snap["seen_tokens"] == 40 and set(snap["kv"]["layer_0"]) == \
        {"ckv"}
    assert b.resume(9, ids[:40].tolist(), kv_state=snap) == {}
    got = [np.asarray(jax.device_get(b.decode_step([9], [int(t)])),
                      np.float32)[0] for t in ids[40:]]
    assert _gap(np.stack(got), _want(params, ids, 40)[1:]) <= F32_TOL


def test_host_tier_spools_and_restores_latent_blocks():
    """A pool of 7 blocks with the host tier on: the first prompt's warm
    blocks are evicted to the host by the second, and restored when the
    first prompt comes again; its logits are the reference's."""
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
    stats = eng.prefix_cache_stats
    assert stats.hit_tokens >= 48 and eng.state_manager.host_tier is not None
    assert _gap(got, _want(params, ids, 50)) <= F32_TOL


def test_prefix_cache_attach_and_fork_on_a_latent_pool():
    """The second request shares the first one's 32-token prefix: its warm
    blocks are attached (the prefill of those positions is skipped), the
    rest forks, and the logits are the reference's."""
    params = _params()
    eng = _engine(params, enable_prefix_cache=True)
    a = _ids(60, seed=6)
    b = np.concatenate([a[:40], _ids(26, seed=7)])
    eng.put([1], [a.tolist()])
    got = _serve(eng, b, 60, uid=2)
    assert eng.prefix_cache_stats.hit_tokens == 32   # two whole blocks
    assert _gap(got, _want(params, b, 60)) <= F32_TOL


@pytest.mark.parametrize("path", ["int8", "tp", "verify_step",
                                  "speculative"])
def test_what_a_latent_row_cannot_do_refuses_by_name(path):
    params = _params()
    if path == "int8":
        with pytest.raises((ValueError, NotImplementedError),
                           match="int8"):
            _engine(params, dtype="int8")
        return
    if path == "tp":
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
        with pytest.raises(NotImplementedError, match="one chip"):
            family.serve_model(HF, BLOCK, mesh=mesh)
        return
    eng = _engine(params)
    eng.put([1], [_ids(20).tolist()])
    if path == "speculative":
        from deepspeed_tpu.serving import SpeculativeConfig

        with pytest.raises(NotImplementedError, match="verify_step"):
            ContinuousBatchScheduler(eng, speculative=SpeculativeConfig())
        return
    with pytest.raises(NotImplementedError, match="latent row"):
        eng.verify_step([1], [[3, 4]])
    # what is a plain copy of pool rows, or a plain step, does not refuse
    assert eng.state_manager.get_sequence(1).seen_tokens == 20
    assert np.asarray(eng.decode_loop([1], [3], 4)).shape == (1, 4)
    assert eng.flush_to_host([1])[1]["seen_tokens"] == 24
    assert eng.generate([_ids(12).tolist()], max_new_tokens=3)[0].shape == (3,)


# ------------------------------------------------------------------ #
# (f) spans, counters and device scopes
# ------------------------------------------------------------------ #
def _serve_a_prompt_and_a_join():
    """100 tokens: chunks of 64 and 36 at a 64-token budget; then a join of
    40 tokens beside the first one's decode.  (engine, the spans)."""
    tracer = Tracer()
    eng = _engine(_params())
    sched = ContinuousBatchScheduler(eng, tracer=tracer)
    first = sched.submit(_ids(100).tolist(), _greedy(8))
    for _ in range(3):
        sched.step()
    sched.submit(_ids(40, seed=4).tolist(), _greedy(3))
    sched.run_until_idle()
    assert len(first.generated) == 8
    return eng, lambda: [r for r in tracer.records() if r.get("ph") == "X"]


def test_counters_on_build_batch_match_a_hand_count():
    _eng, spans = _serve_a_prompt_and_a_join()
    spans = spans()
    built = [r["attrs"] for r in spans if r["name"] == "engine/build_batch"]
    chunks = [a for a in built if a["chunk_seqs"]]
    assert [(a["chunk_tokens"], a["attn_pairs"], a["ctx_rows"])
            for a in chunks] == [
        (64, 64 * 65 // 2, 64),                       # positions 0-63
        (36, 36 * 64 + 36 * 37 // 2, 100),            # 64-99 over 100 rows
        (40, 40 * 41 // 2, 40)]                       # the join
    # the absorbed read's share of those batches: only the join has a
    # one-token row beside its chunk, feeding position 101 (the prompt's
    # 100 and one decode step): blocks 0-6 of 16 rows
    assert [a["row_blocks"] for a in built] == [0, 0, 101 // BLOCK + 1]
    # one-token rows alone: nothing for the expanded read to do
    assert all(a["attn_pairs"] == a["ctx_rows"] == 0
               for a in built if not a["chunk_seqs"])
    dec = [r["attrs"] for r in spans if r["name"] == "decode"]
    assert dec and all(a["read_blocks"] >= 1 for a in dec)


def test_key_step_counters_on_build_batch_match_a_hand_count():
    """``latent_key_steps`` / ``latent_live_key_steps`` on a latent engine's
    ``engine/build_batch`` span: the grid of the expanded read over tiles
    and layers, and its steps that hold a visible key; neither on a batch of
    one-token rows."""
    eng, spans = _serve_a_prompt_and_a_join()
    eng.put([99], [[7]])                  # a ragged batch of one-token rows
    built = [r["attrs"] for r in spans() if r["name"] == "engine/build_batch"]
    # a table of 512 // 16 = 32 entries is ONE step of 512 keys: each of the
    # bucket's four tiles runs a step a layer, a tile that holds a chunk's
    # rows a live one
    layers = HF["num_hidden_layers"]
    assert [(a["bucket"], a["latent_key_steps"], a["latent_live_key_steps"])
            for a in built if a["chunk_seqs"]] == [
        (MAX_SEQS + 64, layers * 4, layers * 4),      # 64 tokens: 4 tiles
        (MAX_SEQS + 64, layers * 4, layers * 3),      # 36 tokens: 3 tiles
        (MAX_SEQS + 64, layers * 4, layers * 3)]      # 40 tokens: 3 tiles
    for a in built:
        if a["chunk_seqs"]:
            assert a["latent_key_steps"] >= a["latent_live_key_steps"] > 0
            assert "chunk_key_steps" not in a         # the k / v pools' own
        else:
            assert "latent_key_steps" not in a \
                and "latent_live_key_steps" not in a
    assert not all(a["chunk_seqs"] for a in built)


def test_device_scopes_of_both_kinds_of_layer():
    eng = _engine(_params(), max_seqs=4)
    eng.put([1], [_ids(70).tolist()])
    eng.decode_step([1], [3])
    text = eng.lower_step(("decode_step",)).as_text(debug_info=True)
    for scope in ("layers_0/attn/q_proj", "layers_0/attn/kv_latent",
                  "layers_1/attn/latent_read", "layers_2/attn/out_proj",
                  "layers_0/mlp", "layers_1/moe/router",
                  "layers_1/moe/dispatch", "layers_2/moe/experts",
                  "layers_1/moe/combine", "layers_2/moe/shared", "lm_head"):
        assert f'"jit(decode_step)/{scope}' in text, scope
    assert "layers_1/mlp" not in text and "layers_0/moe" not in text
    tiled = [k for k in eng.step_keys if k != ("decode_step",)
             and k[0] > 4]
    text = eng.lower_step(tiled[0]).as_text(debug_info=True)
    assert "layers_1/attn/prefill_read" in text
    assert "layers_1/attn/latent_read" in text       # the S one-token rows
    # with the kernels (interpret mode) the expansion has a scope of its own
    eng = _engine(_params(HF_KERNEL), hf=HF_KERNEL, interpret=True,
                  max_seqs=4)
    eng.put([1], [_ids(70).tolist()])
    text = eng.lower_step([k for k in eng.step_keys
                           if k[0] > 4][0]).as_text(debug_info=True)
    for scope in ("attn/expand", "attn/prefill_read", "attn/latent_read"):
        assert f"layers_2/{scope}" in text, scope


# ------------------------------------------------------------------ #
# each kernel against its composition, interpret mode, a ragged batch whose
# tiles belong to three sequences
# ------------------------------------------------------------------ #
def _latent_case(seed=0):
    rng = np.random.default_rng(seed)
    bs, h, rank, nope, rope, vd, w, s_count, b = 16, 4, 128, 128, 64, 128, \
        256, 6, 8
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f32((s_count * b + 1) * bs, w).at[:, rank + rope:].set(0)
    w_kvb = f32(rank, h * (nope + vd)) * rank ** -0.5
    tables = jnp.asarray(
        (rng.permutation(s_count * b) + 1).reshape(s_count, b), jnp.int32)
    return bs, h, rank, nope, rope, vd, w, pool, w_kvb, tables, f32


def test_latent_decode_walk_with_pad_rows_and_unordered_slots():
    bs, h, rank, nope, rope, vd, w, pool, _w, tables, f32 = _latent_case()
    slot = jnp.asarray([4, 0, 0, 2, 5, 1, 3, 0], jnp.int32)
    pos = jnp.asarray([100, -1, 15, 16, 127, -1, 0, 63], jnp.int32)
    q = f32(8, h, w) * 0.3
    scale = (nope + rope) ** -0.5
    got = lf.latent_decode_attention(q, pool, tables, slot, pos,
                                     block_size=bs, value_dim=rank,
                                     scale=scale, interpret=True)
    want = rd.absorbed_read_xla(q, pool, tables, slot, pos, bs, rank, scale)
    real = np.asarray(pos) >= 0
    assert np.max(np.abs(np.asarray(got - want)[real])) <= 1e-5
    assert not np.asarray(got)[~real].any()          # pad rows: zeros


def test_expand_and_prefill_kernels_over_tiles_of_three_sequences():
    bs, h, rank, nope, rope, vd, w, pool, w_kvb, tables, f32 = \
        _latent_case(1)
    tile, t_rows = 16, 8 * 16
    slot = np.zeros((t_rows,), np.int32)
    pos = np.full((t_rows,), -1, np.int32)
    # a 40-token chunk from position 50 (three tiles, the last with 8 pad
    # rows), a whole tile from 0, a 5-token tail from 120, then pad tiles
    for start, seq, first, n in ((0, 5, 50, 40), (48, 2, 0, 16),
                                 (64, 0, 120, 5)):
        slot[start:start + n] = seq
        pos[start:start + n] = np.arange(first, first + n)
    slot, pos = jnp.asarray(slot), jnp.asarray(pos)
    q_nope, q_pe = f32(t_rows, h, nope), f32(t_rows, h, rope)
    scale = (nope + rope) ** -0.5
    kv, plan = lf.latent_expand(pool, w_kvb, tables, slot, pos,
                                block_size=bs, tile_q=tile, rank=rank,
                                interpret=True)
    _s, maxpos, owner, blocks = (np.asarray(a) for a in plan)
    assert owner.tolist() == [0, 0, 0, 3, 4, 5, 6, 7]
    assert blocks.tolist() == [6, 0, 0, 1, 8, 0, 0, 0]   # 90 // 16 + 1 ...
    assert maxpos.tolist() == [65, 81, 89, 15, 124, -1, -1, -1]
    q_cat = jnp.concatenate(
        [q_nope, q_pe, jnp.zeros((t_rows, h, 128 - rope))], -1)
    got = lf.latent_prefill_attention(q_cat, kv, plan, pos, block_size=bs,
                                      tile_q=tile, nope=nope, v_dim=vd,
                                      scale=scale, interpret=True)
    want = rd.expanded_read_xla(q_nope, q_pe, pool, w_kvb, tables, slot,
                                pos, bs, rank, scale)
    real = np.asarray(pos) >= 0
    assert np.max(np.abs(np.asarray(got - want)[real])) <= 2e-5
    assert not np.asarray(got)[~real].any()


# the expanded read's key step (PR 45): ``kb`` table entries a grid step,
# lane-wise statistics, the mask on edge steps only, never-written blocks.
# (block_size, table width, tile_q, chunks as (first row, slot, start,
# tokens)); ``kb`` = 512 keys where the table holds them.
_STEP_CASES = {
    # kb 32 of 40 entries (no multiple): a chunk from inside block 31 whose
    # context crosses the step at key 512; its second tile (516-531) is
    # wholly inside on step 0 and on the diagonal on step 1, its third ends
    # in pad rows; a second chunk that lives in the first step alone
    "two_steps_of_small_blocks": (16, 40, 16, (
        (0, 3, 500, 40), (48, 1, 7, 30), (96, 4, 0, 16))),
    # the same table under a tile of two blocks
    "tile_of_two_blocks": (16, 40, 32, (
        (0, 2, 470, 90), (96, 5, 20, 33))),
    # blocks of a lane tile: four lane tiles of statistics a step, kb 4 of 6
    # entries; tile 1 (528-655) inside on step 0, diagonal on step 1; the
    # short chunk's step holds three entries nobody wrote
    "lane_tiles": (128, 6, 128, (
        (0, 1, 400, 300), (384, 0, 0, 100))),
    # a table under a lane tile of keys: the plain-column statistics
    "column_statistics": (16, 5, 16, (
        (0, 2, 30, 40), (48, 0, 0, 9))),
}


@functools.lru_cache(maxsize=None)
def _step_case(name):
    bs, width, tile, chunks = _STEP_CASES[name]
    rng = np.random.default_rng(45)
    h, rank, nope, rope, vd, w, s_count = 2, 128, 128, 64, 128, 256, 6
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f32((s_count * width + 1) * bs, w).at[:, rank + rope:].set(0)
    w_kvb = f32(rank, h * (nope + vd)) * rank ** -0.5
    tables = jnp.asarray((rng.permutation(s_count * width) + 1).reshape(
        s_count, width), jnp.int32)
    t_rows = -(-max(r + n for r, _s, _a, n in chunks) // tile) * tile + tile
    slot = np.zeros((t_rows,), np.int32)
    pos = np.full((t_rows,), -1, np.int32)
    for row, seq, start, n in chunks:
        slot[row:row + n], pos[row:row + n] = seq, np.arange(start,
                                                             start + n)
    slot, pos = jnp.asarray(slot), jnp.asarray(pos)
    q_nope, q_pe = f32(t_rows, h, nope), f32(t_rows, h, rope)
    scale = (nope + rope) ** -0.5
    kv, plan = lf.latent_expand(pool, w_kvb, tables, slot, pos,
                                block_size=bs, tile_q=tile, rank=rank,
                                interpret=True)
    q_cat = jnp.concatenate(
        [q_nope, q_pe, jnp.zeros((t_rows, h, 128 - rope))], -1)
    read = functools.partial(
        lf.latent_prefill_attention, q_cat, plan=plan, token_pos=pos,
        block_size=bs, tile_q=tile, nope=nope, v_dim=vd, scale=scale,
        interpret=True)
    want = rd.expanded_read_xla(q_nope, q_pe, pool, w_kvb, tables, slot,
                                pos, bs, rank, scale)
    return kv, plan, read, want, np.asarray(pos) >= 0


@pytest.mark.parametrize("name", _STEP_CASES)
def test_expanded_read_key_steps_match_the_composition(name):
    bs, width, tile, chunks = _STEP_CASES[name]
    kv, _plan, read, want, real = _step_case(name)
    kb = lf._latent_step_blocks(bs, width, tile)
    assert kb == min(512 // bs, width)
    # the case is what its comment says: a context past one step, a table
    # that is no multiple of the step (or one step of a lane tile's part)
    if width > kb:
        assert width % kb and max(a + n for _r, _s, a, n in chunks) \
            > kb * bs
    got = read(expanded=kv)
    assert np.max(np.abs(np.asarray(got - want)[real])) <= 2e-5
    assert not np.asarray(got)[~real].any()          # pad rows: zeros


@pytest.mark.parametrize("name", _STEP_CASES)
def test_never_written_blocks_do_not_reach_the_output(name):
    """``latent_expand`` writes a chunk's blocks up to its last position; a
    key step reads whole runs of ``kb`` entries.  Every block nobody wrote,
    set to NaN: the same finite answer."""
    bs, width, _tile, _chunks = _STEP_CASES[name]
    kv, plan, read, want, real = _step_case(name)
    blocks = np.asarray(plan[3])
    unwritten = (np.arange(width)[None, :] >= blocks[:, None]).reshape(-1)
    assert unwritten.sum() > unwritten.size // 2
    poisoned = jnp.where(jnp.asarray(unwritten)[:, None, None], jnp.nan, kv)
    got = np.asarray(read(expanded=poisoned))
    assert np.isfinite(got).all()
    assert np.max(np.abs((got - np.asarray(want))[real])) <= 2e-5
    assert np.array_equal(got, np.asarray(read(expanded=kv)))


def test_latent_prefill_key_steps_equal_a_hand_count():
    # the cell's shape: a 1,024-token chunk from 3,072 over 60 entries of
    # 128, tile 128: four entries a step, 15 steps a tile; tile i ends at
    # 3,072 + 128 (i + 1) - 1, so tiles 0-3 see 7 steps and 4-7 see 8
    assert lf.latent_prefill_key_steps(
        [(3072, 1024)], 8, block_size=128, entries=60, tile_q=128) \
        == (8 * 15, 4 * 7 + 4 * 8)
    # two chunks and a pad tile; the last tile of the first holds 44 rows
    assert lf.latent_prefill_key_steps(
        [(400, 300), (0, 100)], 5, block_size=128, entries=6, tile_q=128) \
        == (5 * 2, (2 + 2 + 2) + 1)
    # a table of one step: every tile with a chunk is one live step
    assert lf.latent_prefill_key_steps(
        [(50, 40), (0, 16)], 6, block_size=16, entries=8, tile_q=16) \
        == (6, 3 + 1)


# ------------------------------------------------------------------ #
# (g) the loader: the rope dims de-interleaved, by name
# ------------------------------------------------------------------ #
def test_loader_deinterleaves_the_rope_dims():
    from deepspeed_tpu.checkpoint import hf_loader

    cfg = {"qk_nope_head_dim": 4, "qk_rope_head_dim": 6, "kv_lora_rank": 5}
    rules = {r[0]: r[1] for r in hf_loader._deepseek_v3_rules()}
    q_rule = [fn for pat, fn in rules.items() if "q_proj" in pat][0]
    kva_rule = [fn for pat, fn in rules.items() if "kv_a_proj" in pat][0]
    import re

    m = re.match(r"^model\.layers\.(\d+)\..*$",
                 "model.layers.3.self_attn.q_proj.weight")
    path, tf = q_rule(m)
    assert path == ("layers_3", "self_attn", "q_proj", "kernel")
    # two heads of 4 + 6 outputs over 3 inputs; output row r holds r
    w = np.repeat(np.arange(20.0)[:, None], 3, axis=1)
    assert tf(w, cfg)[0].tolist() == [
        0, 1, 2, 3, 4, 6, 8, 5, 7, 9,
        10, 11, 12, 13, 14, 16, 18, 15, 17, 19]
    path, tf = kva_rule(m)
    assert path[-2:] == ("kv_a_proj_with_mqa", "kernel")
    w = np.repeat(np.arange(11.0)[:, None], 3, axis=1)
    assert tf(w, cfg)[0].tolist() == [0, 1, 2, 3, 4, 5, 7, 9, 6, 8, 10]


def test_hf_checkpoint_round_trip(tmp_path):
    """A tiny ``DeepseekV3ForCausalLM`` saved by transformers, loaded by
    name (the rope dims of ``q_proj`` and ``kv_a_proj_with_mqa``
    de-interleaved, experts stacked, the selection bias kept), served by
    ``InferenceEngineV2.from_hf``: the engine, the plain reference and the
    published implementation agree."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "DeepseekV3ForCausalLM"):
        pytest.skip("this transformers has no deepseek_v3")
    from deepspeed_tpu.checkpoint.hf_loader import load_hf_checkpoint

    hf = {k: v for k, v in HF.items()
          if k not in ("router_experts", "expert_start")}
    hf.update(n_routed_experts=8, num_key_value_heads=4,
              tie_word_embeddings=False, hidden_act="silu",
              attention_bias=False, num_nextn_predict_layers=0)
    torch.manual_seed(0)
    hf_cfg = transformers.DeepseekV3Config(
        **{k: v for k, v in hf.items() if k != "model_type"},
        rope_scaling=None)
    hf_model = transformers.DeepseekV3ForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in hf_model.named_parameters():
            if name.endswith("norm.weight"):
                p.uniform_(0.5, 1.5)
            elif name.endswith("mlp.gate.weight"):
                p.normal_(0.0, 2.0 * hf["hidden_size"] ** -0.5)
            elif p.ndim >= 2:
                p.normal_(0.0, p.shape[-1] ** -0.5)
        for name, buf in hf_model.named_buffers():
            if name.endswith("e_score_correction_bias"):
                buf.normal_(0.0, 0.3)
    hf_cfg.save_pretrained(tmp_path)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    want_shapes = jax.tree.map(lambda a: a.shape,
                               rd.param_shapes(_config(jnp.float32, hf)))
    assert jax.tree.map(lambda a: a.shape, params) == want_shapes

    ids = _ids(40, seed=9)
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids[None])).logits.numpy()[0]
    ref = reference.logits_at(_ref_params(params), ids, hf,
                              rows=list(range(len(ids))))
    assert _gap(ref, theirs) <= F32_TOL
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 128,
                              "max_ragged_sequence_count": 2,
                              "max_context": 64},
            "kv_cache": {"block_size": 8}}), dtype=jnp.float32)
    got = _serve(eng, ids, n_prompt=34)
    assert _gap(got, theirs[33:]) <= F32_TOL
