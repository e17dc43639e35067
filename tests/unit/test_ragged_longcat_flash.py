"""LongCat-Flash (``model_type: longcat_flash``) through the normal serving
path at a small size on the CPU: ``RaggedLongcatFlash`` (a double block of
two latent-attention sub-layers and two dense FFNs, a shortcut-connected
routed branch with zero-compute experts) -> ``InferenceEngineV2`` (``put``,
``decode_step``, two-segment batches, TWO cache layers a published layer) ->
``ContinuousBatchScheduler``, against the benchmark's plain float32
reference (``benchmark/reference/longcat_flash.py``).

What makes the model what it is is drawn away from its neutral value (norm
weights uniform in 0.5 .. 1.5, a selection bias of the scores' own size) so
that leaving it out fails.
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

from benchmark.families import longcat_flash as family      # noqa: E402
from benchmark.reference import longcat_flash as reference   # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_longcat_flash as rl)
from deepspeed_tpu.inference.v2.modules.moe import (         # noqa: E402
    moe_router, zero_expert_moe)
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)
from longcat_faults import FAULTS, fault                     # noqa: E402

ZERO = 4
HF = {"model_type": "longcat_flash", "vocab_size": 256, "hidden_size": 64,
      "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
      "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48,
      "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
      "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
      "n_routed_experts": 4, "router_experts": 8, "expert_start": 2,
      "zero_expert_num": ZERO, "zero_expert_type": "identity",
      "moe_topk": 3, "routed_scaling_factor": 6, "rope_theta": 10000000,
      "rms_norm_eps": 1e-5, "max_position_embeddings": 512,
      "attention_bias": False, "attention_method": "MLA"}
# widths the Mosaic kernels can tile (interpret mode runs them here)
HF_KERNEL = dict(HF, num_attention_heads=2, kv_lora_rank=128,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
MAX_SEQS, BUDGET, TILE, BLOCK = 8, 64, 16, 16
# the same float32 mathematics in another order
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
        rl.param_shapes(_config(jnp.float32, hf)))
    width = hf.get("router_experts", hf["n_routed_experts"]) \
        + hf["zero_expert_num"]
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] == "e_score_correction_bias":
            a = rng.standard_normal(shape) / width    # the scores' size
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
        lp["bias"] = lp["bias"] / family.BIAS_STD
    return ref


def _engine(params, act=jnp.float32, hf=HF, blocks=120, max_context=512,
            max_seqs=MAX_SEQS, budget=BUDGET, tile=TILE, interpret=None,
            **kv):
    model = rl.RaggedLongcatFlash(_config(act, hf), BLOCK)
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
# (a) engine against reference: chunks across a tile boundary, then decode
# through both sub-layers' caches
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, hf, tile, budget, interpret", [
    (20, HF, TILE, BUDGET, None),       # one chunk
    (100, HF, TILE, BUDGET, None),      # two chunks: 64 + 36
    (100, HF, 128, 60, None),           # no tiles: rows packed back to back
    (100, HF_KERNEL, TILE, BUDGET, True),   # the three kernels, interpreted
], ids=["1chunk", "2chunks", "untiled", "2chunks-kernels-interpreted"])
def test_f32_engine_matches_reference(n_prompt, hf, tile, budget, interpret):
    params = _params(hf)
    ids = _ids(n_prompt + 6)
    eng = _engine(params, hf=hf, tile=tile, budget=budget,
                  interpret=interpret)
    assert (eng._prefill_tile() is None) == (tile == 128)
    assert _gap(_serve(eng, ids, n_prompt),
                _want(params, ids, n_prompt, hf)) <= F32_TOL


def test_bf16_engine_is_the_same_model():
    """bf16 engine against the float32 reference on the same bf16-rounded
    weights, at the family's seeding of what a routing flip weighs (the
    residual-writing kernels at ``RESIDUAL_SCALE``, the experts' down
    projections at ``EXPERT_DOWN`` of it) and a router as wide as 32
    outputs at scale 1 (a flip between a zero output and an expert held
    elsewhere moves a row by ``w m``; at this router's ``w`` of 0.5 one
    flip would be the whole reading)."""
    hf = dict(HF, router_experts=24, zero_expert_num=8,
              routed_scaling_factor=1)
    params = _params(hf, seed=2)
    for i in range(hf["num_layers"]):
        lp = params[f"layers_{i}"]
        for j in (0, 1):
            for leaf in (lp[f"sub_{j}"]["self_attn"]["o_proj"],
                         lp[f"sub_{j}"]["mlp"]["down_proj"]):
                leaf["kernel"] = leaf["kernel"] * family.RESIDUAL_SCALE
        lp["mlp"]["experts"]["w_down"] = lp["mlp"]["experts"]["w_down"] \
            * family.RESIDUAL_SCALE * family.EXPERT_DOWN
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    ids = _ids(106, seed=5)
    got = _serve(_engine(params, act=jnp.bfloat16, hf=hf), ids, 100)
    assert _gap(got, _want(params, ids, 100, hf)) <= BF16_TOL


# ------------------------------------------------------------------ #
# (b) each fault of the chip's table, seen at float32
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("name", FAULTS)
def test_a_fault_moves_the_logits(name):
    params = _params()
    ids = _ids(106)
    with fault(name, zero_experts=ZERO):
        got = _serve(_engine(params), ids, 100)
    assert _gap(got, _want(params, ids, 100)) > BF16_TOL


def test_the_faults_are_faults_of_a_clean_program():
    params = _params()
    ids = _ids(106)
    with fault("clean"):
        got = _serve(_engine(params), ids, 100)
    assert _gap(got, _want(params, ids, 100)) <= F32_TOL
    with pytest.raises(ValueError, match="unknown fault"):
        with fault("no_such_fault"):
            pass


# ------------------------------------------------------------------ #
# (c) the router and the zero-compute experts
# ------------------------------------------------------------------ #
def test_router_against_a_hand_count():
    """Softmax over ALL outputs, the bias in the selection only, weights
    the unbiased scores x 6, no renormalisation, ties to the lowest
    index."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0, 1.0, 0.5],
                       [0.0, 0.0, 0.0, 0.0, 0.0, 0.0]], np.float32)
    bias = np.array([-1.0, 0.0, 0.0, 0.5, 0.0, 0.0], np.float32)
    # identity router: x W = x
    topi, w = moe_router(jnp.asarray(logits), jnp.eye(6, dtype=jnp.float32),
                         3, bias=jnp.asarray(bias), routed_scale=6.0,
                         scoring="softmax")
    s = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    # row 0: s + b = [.49-1, .18, .066, .024+.5, .18, .11]: outputs 3, 1, 4
    # (1 before 4: a tie goes to the lower index); the best score, output
    # 0, is biased out and weighs nothing
    assert np.asarray(topi).tolist() == [[3, 1, 4], [3, 1, 2]]
    assert np.allclose(np.asarray(w)[0], 6 * s[0, [3, 1, 4]], rtol=1e-6)
    # row 1: all equal: the biased one, then the lowest indices but 0
    assert np.allclose(np.asarray(w)[1], 6 / 6, rtol=1e-6)
    assert not np.isclose(np.asarray(w)[0].sum(), 6.0)   # not renormalised
    # the reference's own router is the same function
    ri, rw = reference.route(jnp.asarray(logits), jnp.eye(6), jnp.asarray(
        bias), 3, 6.0)
    assert np.asarray(ri).tolist() == np.asarray(topi).tolist()
    assert np.allclose(np.asarray(rw), np.asarray(w), rtol=1e-6)
    # without ``scoring`` a bias still means the sigmoid router
    _, ws = moe_router(jnp.asarray(logits), jnp.eye(6, dtype=jnp.float32),
                       3, bias=jnp.asarray(bias), renormalize=False)
    assert np.allclose(np.asarray(ws)[1], 0.5)


def _branch(seed=4, h=64, f=32, e=32, z=16, k=6, t=50):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x = f32(t, h)
    moe = {"gate": {"wg": {"kernel": 2.0 * f32(h, e + z) * h ** -0.5},
                    "e_score_correction_bias": f32(e + z) / (e + z)},
           "experts": {"w_gate": f32(e, h, f) * h ** -0.5,
                       "w_up": f32(e, h, f) * h ** -0.5,
                       "w_down": f32(e, f, h) * f ** -0.5}}
    return x, moe, (e, z, k)


def _oracle(x, moe, e, k, zero_term=True):
    """The branch by the reference's plain functions over ALL experts."""
    with jax.default_matmul_precision("highest"):
        idx, w = reference.route(x, moe["gate"]["wg"]["kernel"],
                                 moe["gate"]["e_score_correction_bias"],
                                 k, 6.0)
        y = reference.experts_part(x, moe["experts"], idx, w, 0)
        if zero_term:
            y = y + reference.zero_part(x, idx, w, e)
    return np.asarray(y), np.asarray(idx), np.asarray(w)


def test_all_experts_held_plus_zero_experts_is_the_dense_oracle():
    x, moe, (e, z, k) = _branch()
    want, idx, w = _oracle(x, moe, e, k)
    got, counts = zero_expert_moe(x, moe, k, jnp.float32, z,
                                  routed_scale=6.0,
                                  real=jnp.ones((x.shape[0],), bool))
    assert np.max(np.abs(np.asarray(got) - want)) \
        <= 1e-5 * np.max(np.abs(want))
    # every slot is an expert here or a zero output
    assert np.asarray(counts).tolist() == [
        x.shape[0] * k, int((idx >= e).sum()), int((idx < e).sum())]
    assert 0 < (idx >= e).sum() < idx.size
    # the zero term is w m, no more: without it the oracle differs by it
    dry, _, _ = _oracle(x, moe, e, k, zero_term=False)
    zsum = np.where(idx >= e, w, 0.0).sum(-1)
    assert np.allclose(want - dry, zsum[:, None] * np.asarray(x),
                       atol=1e-5)


def test_thirty_two_expert_shares_add_up_to_the_uncut_layer():
    """512 experts over 32 chips at a sixteenth of the count (32 experts in
    32 shares of one... here 32 shares of ONE expert each) beside 16
    zero-compute outputs at top-6: the shares' routed parts, with the zero
    term (every chip computes it for its own tokens) counted ONCE, add up to
    the uncut reference layer; and a tiny double-block layer with both dense
    FFNs counted once does the same through the model's own forward."""
    x, moe, (e, z, k) = _branch()
    want, idx, w = _oracle(x, moe, e, k)
    zero = np.where(idx >= e, w, 0.0).sum(-1)[:, None] * np.asarray(x)

    def share(s):
        part = {"gate": moe["gate"], "experts": {
            n: m[s:s + 1] for n, m in moe["experts"].items()}}
        y, _ = zero_expert_moe(x, part, k, jnp.float32, z, expert_start=s,
                               routed_scale=6.0)
        return np.asarray(y)

    parts = [share(s) for s in range(32)]
    total = sum(p - zero for p in parts) + zero
    assert np.max(np.abs(total - want)) <= 1e-5 * np.max(np.abs(want))
    # a share alone is a part; the zero term 32 times is not the layer
    assert np.max(np.abs(parts[3] - want)) > 0.05 * np.max(np.abs(want))
    assert np.max(np.abs(sum(parts) - want)) > 0.5 * np.max(np.abs(want))


def test_the_shares_of_a_layer_add_up_through_the_reference():
    """The reference's own share (``expert_start``, ``zero_term``): the
    residual stream after ONE published layer is linear in the branch's
    result, so with attention, both dense FFNs and the zero term counted
    once (share 0 keeps them, the others contribute their routed part
    alone: their stream less the stream without any expert) the 4 shares of
    8 experts add up to the uncut layer."""
    hf = dict(HF, num_layers=1, n_routed_experts=8, router_experts=8,
              expert_start=0)
    params = _params(hf, seed=6)
    ids = _ids(40, seed=7)
    ref = _ref_params(params)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(reference.hidden(ref, ids, hf))

        def cut(start, count, zero_term):
            lp = dict(ref["layers"][0])
            for n in ("w_gate", "w_up", "w_down"):
                lp[n] = lp[n][start:start + count]
            return np.asarray(reference.hidden(
                {**ref, "layers": [lp]}, ids,
                dict(hf, expert_start=start), zero_term=zero_term))

        none = cut(0, 0, False)                # attention and dense FFNs
        total = cut(0, 2, True) + sum(
            cut(s, 2, False) - none for s in (2, 4, 6))
    assert np.max(np.abs(total - whole)) <= 1e-5 * np.max(np.abs(whole))
    assert np.max(np.abs(cut(0, 2, True) - whole)) \
        > 0.01 * np.max(np.abs(whole))


def test_published_keys_are_read_and_another_zero_expert_is_refused():
    cfg = family.program_config(HF)
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.held_experts,
            cfg.expert_start, cfg.zero_expert_num, cfg.moe_topk) \
        == (2, 8, 4, 2, 4, 3)
    assert cfg.q_lora_rank == 48 and cfg.index_topk is None
    assert cfg.q_scale == pytest.approx((64 / 48) ** 0.5)
    assert cfg.kv_scale == pytest.approx(2 ** 0.5)
    assert cfg.row_width == 128
    off = family.program_config(dict(HF, mla_scale_q_lora=False,
                                     mla_scale_kv_lora=False))
    assert (off.q_scale, off.kv_scale) == (1.0, 1.0)
    with pytest.raises(NotImplementedError, match="zero_expert_type"):
        family.program_config(dict(HF, zero_expert_type="copy"))
    with pytest.raises(NotImplementedError, match="one chip"):
        rl.RaggedLongcatFlash(cfg, BLOCK, mesh=object())
    assert reference.scales(HF) == (pytest.approx((64 / 48) ** 0.5),
                                    pytest.approx(2 ** 0.5))


def test_parameter_counts_of_the_cell_and_of_the_whole_model():
    """5,172,749,312 parameters at the cell's cut (10.35 GB in bf16) and
    560.66 B for the published model, from the program's own shapes and
    from the family's count by part."""
    import json

    with open(os.path.join(_REPO, "benchmark", "configs",
                           "longcat-flash-omni-serve-1chip.json")) as f:
        cell = json.load(f)
    shapes = family.serve_param_shapes(cell)
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    assert n == 5_172_749_312 == family.param_counts(cell)["total"]
    parts = family.param_counts(cell)
    assert (parts["mla"], parts["ffn"], parts["router"], parts["norms"],
            parts["expert"]) == (90_572_800, 226_492_416, 4_719_360, 24_576,
                                 37_748_736)
    assert parts["layer"] == 1_242_854_144
    whole = dict(cell, num_layers=28, n_routed_experts=512,
                 vocab_size=131072)
    assert family.param_counts(whole)["total"] == 28 * (
        638_874_368 + 512 * 37_748_736) + 2 * 805_306_368 + 6_144
    assert round(family.param_counts(whole)["total"] / 1e9, 2) == 560.66
    s = family.shapes(cell)
    assert (s["layers"], s["moe_layers"], s["experts"], s["router_width"],
            s["zero_experts"]) == (8, 4, 16, 768, 256)
    assert s["kv_row_bytes_per_token"] == 10_240
    assert s["total_params"] == n


# ------------------------------------------------------------------ #
# (d) two cache layers a published layer, through the block operations
# ------------------------------------------------------------------ #
def test_cache_layers_are_attention_sub_layers():
    eng = _engine(_params())
    kv = eng.state_manager.kv_cache
    assert eng.model.num_layers == 4 == 2 * HF["num_layers"]
    assert sorted(kv.cache) == [f"layer_{i}" for i in range(4)]
    assert kv.kv_row == {"ckv": 128}
    assert kv.per_token_bytes == 4 * 128 * 4            # float32 here
    ids = _ids(40)
    eng.put([1], [ids.tolist()])
    seq = eng.state_manager.get_sequence(1)
    rows = np.asarray(seq.blocks)[:, None] * BLOCK + np.arange(BLOCK)
    written = [np.asarray(kv.cache[f"layer_{i}"]["ckv"])[
        rows.reshape(-1)[:40]] for i in range(4)]
    # every sub-layer wrote its own rows, and no two alike
    assert all(np.abs(w).sum() > 0 for w in written)
    assert all(not np.allclose(written[i], written[j])
               for i in range(4) for j in range(i))


def test_block_copies_gathers_and_scatters_carry_every_sub_layer():
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
    assert sorted(payload) == [f"layer_{i}" for i in range(4)]
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


def test_flush_to_host_and_resume_carry_every_sub_layer():
    params = _params()
    ids = _ids(66, seed=11)
    a, b = _engine(params), _engine(params)
    a.put([1], [ids[:60].tolist()])
    snap = a.flush_to_host([1], include_kv=True)[1]
    assert snap["seen_tokens"] == 60 and len(snap["kv"]) == 4
    assert b.resume(9, ids[:60].tolist(), kv_state=snap) == {}
    assert _gap(_decode(b, ids[60:], 9),
                _want(params, ids, 60)[1:]) <= F32_TOL


def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


def _solo(eng, prompt, n_new):
    sched = ContinuousBatchScheduler(eng)
    req = sched.submit(list(prompt), _greedy(n_new))
    sched.run_until_idle()
    return list(req.generated)


def test_a_preemption_by_recompute_rebuilds_every_sub_layer():
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


# ------------------------------------------------------------------ #
# (e) the counters the device decides, and the device scopes
# ------------------------------------------------------------------ #
def _hand_count(params, ids_by_row, hf=HF):
    """(zero slots, held rows) of the LAST position of each sequence in
    ``ids_by_row`` and of every position (``all``), over the layers, by the
    reference's router on the reference's own stream."""
    ref = _ref_params(params)
    e = hf["router_experts"]
    lo, hi = hf["expert_start"], hf["expert_start"] + hf["n_routed_experts"]
    last, every = np.zeros(2, int), np.zeros(2, int)
    for ids in ids_by_row:
        ids = np.asarray(ids)
        for n in range(1, hf["num_layers"] + 1):
            sub = dict(hf, num_layers=n)
            with jax.default_matmul_precision("highest"):
                # the stream that enters layer n's branch: after sub_0's
                # attention of that layer
                x = reference.hidden({**ref, "layers": ref["layers"][:n - 1]},
                                     ids, sub) if n > 1 else \
                    reference._embed(ref["embed"], ids.astype(np.int32))
                lp = ref["layers"][n - 1]
                sp = lp["subs"][0]
                s_q, s_kv = reference.scales(hf)
                x = reference._attn_sub(
                    x, {k: sp[k] for k in reference._ATTN_KEYS},
                    hq=hf["num_attention_heads"], rank=hf["kv_lora_rank"],
                    nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
                    vd=hf["v_head_dim"], eps=1e-5, latent_eps=1e-6,
                    theta=float(hf["rope_theta"]), s_q=s_q, s_kv=s_kv,
                    q_block=len(ids))
                m = reference._rms(x, sp["ln2"].astype(jnp.float32), 1e-5)
                idx, _ = reference.route(m, lp["router"], lp["bias"],
                                         hf["moe_topk"], 6.0)
            idx = np.asarray(idx)
            z, h = idx >= e, (idx >= lo) & (idx < hi)
            last += (z[-1].sum(), h[-1].sum())
            every += (z.sum(), h.sum())
    return last, every


def test_the_three_counters_match_a_hand_count_with_pad_rows_present():
    """``put`` of two prompts (a bucket with pad rows in both segments),
    then decode steps of two rows among ``max_seqs`` = 8: the counters that
    come behind the tokens count the REAL rows alone."""
    params = _params()
    a, b = _ids(20, seed=1).tolist(), _ids(37, seed=2).tolist()
    tracer = Tracer()
    eng = _engine(params)
    eng.attach_tracer(tracer)
    assert eng.step_counters == ("moe_slots", "moe_zero_slots",
                                 "moe_held_rows")
    out = eng.put([1, 2], [a, b], greedy=True)
    fetch = [r["attrs"] for r in tracer.records()
             if r.get("ph") == "X" and r["name"] == "fetch"]
    _, every = _hand_count(params, [a, b])
    k, layers = HF["moe_topk"], HF["num_layers"]
    # (two batches: the prompts' tiles do not fit one 64-row budget)
    assert [a["launch"] for a in fetch] == [1, 2]
    assert {n: sum(a[n] for a in fetch) for n in eng.step_counters} == {
        "moe_slots": 57 * k * layers, "moe_zero_slots": int(every[0]),
        "moe_held_rows": int(every[1])}
    # a decode step: its vector is max_seqs tokens, then the counters
    _, nxt = eng.decode_step([1, 2], [out[1], out[2]], greedy=True)
    host = np.asarray(nxt)
    assert host.shape == (MAX_SEQS + 3,)
    last, _ = _hand_count(params, [a + [out[1]], b + [out[2]]])
    assert eng.counters_of(host) == {
        "moe_slots": 2 * k * layers, "moe_zero_slots": int(last[0]),
        "moe_held_rows": int(last[1])}
    # fed the vector as it is (device tokens, counters behind): the same
    # step as fed the two tokens from the host
    _, again = eng.decode_step([1, 2], nxt, greedy=True)
    other = _engine(params)
    other.put([1, 2], [a, b], greedy=True)
    other.decode_step([1, 2], [out[1], out[2]])
    _, want = other.decode_step([1, 2], host[:2].tolist(), greedy=True)
    again, want = np.asarray(again), np.asarray(want)
    # (the pad rows were fed other tokens: their argmax is nobody's)
    assert again[:2].tolist() == want[:2].tolist()
    assert again[-3:].tolist() == want[-3:].tolist()


def test_the_scheduler_puts_the_counters_on_the_fetch_that_brings_them():
    tracer = Tracer()
    eng = _engine(_params())
    sched = ContinuousBatchScheduler(eng, tracer=tracer)
    reqs = [sched.submit(_ids(30 + 9 * i, seed=20 + i).tolist(), _greedy(6))
            for i in range(3)]
    sched.run_until_idle()
    assert all(len(r.generated) == 6 for r in reqs)
    spans = [r for r in tracer.records() if r.get("ph") == "X"]
    fetch = [r["attrs"] for r in spans if r["name"] == "fetch"]
    assert fetch and all("moe_slots" in a and "launch" in a for a in fetch)
    k, layers = HF["moe_topk"], HF["num_layers"]
    # every token fed (prompts, and each generated token but the last) was
    # routed once a layer, whatever batch it rode in, pad rows never
    fed = sum(30 + 9 * i for i in range(3)) + 3 * 5
    launches = {a["launch"]: a for a in fetch}
    assert sum(a["moe_slots"] for a in launches.values()) \
        == fed * k * layers
    assert all(0 <= a["moe_zero_slots"] <= a["moe_slots"]
               and 0 <= a["moe_held_rows"] <= a["moe_slots"]
               - a["moe_zero_slots"] for a in fetch)
    assert sum(a["moe_zero_slots"] for a in fetch) > 0


def test_a_model_without_counters_keeps_its_token_vector():
    from deepspeed_tpu.inference.v2.model_implementations import \
        ragged_deepseek_v3 as rd
    from benchmark.families import moonlight

    hf = {"vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
          "moe_intermediate_size": 16, "num_hidden_layers": 2,
          "num_attention_heads": 2, "kv_lora_rank": 16, "q_lora_rank": None,
          "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
          "n_routed_experts": 4, "n_shared_experts": 1,
          "num_experts_per_tok": 2, "first_k_dense_replace": 1,
          "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "routed_scaling_factor": 2.0,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
          "max_position_embeddings": 128}
    cfg = moonlight.program_config(hf)
    cfg.dtype = jnp.float32
    assert (cfg.q_scale, cfg.kv_scale) == (1.0, 1.0)
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda l: jnp.asarray(0.1 * rng.standard_normal(l.shape),
                              jnp.float32), rd.param_shapes(cfg))
    eng = InferenceEngineV2(
        rd.RaggedDeepseekV3(cfg, BLOCK), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": 32,
                              "max_ragged_sequence_count": 4,
                              "max_context": 64},
            "kv_cache": {"block_size": BLOCK, "num_blocks": 12}}))
    assert eng.step_counters == () and eng.counters_of([1, 2, 3, 4]) == {}
    tok = eng.put([1], [[1, 2, 3]], greedy=True)[1]
    _, nxt = eng.decode_step([1], [tok], greedy=True)
    assert nxt.shape == (4,)
    text = eng.lower_step(("decode_step",)).as_text()
    assert "concatenate" not in text.split("sample_argmax")[-1][:2000]


def test_device_scopes_of_the_double_block_and_the_branch():
    eng = _engine(_params(), max_seqs=4)
    eng.put([1], [_ids(70).tolist()])
    eng.decode_step([1], [3])
    text = eng.lower_step(("decode_step",)).as_text(debug_info=True)
    for scope in ("layers_0/sub_0/attn/q_proj", "layers_0/sub_1/attn/q_proj",
                  "layers_1/sub_0/attn/kv_latent",
                  "layers_1/sub_1/attn/latent_read",
                  "layers_0/sub_1/attn/out_proj", "layers_0/sub_0/mlp",
                  "layers_1/sub_1/mlp", "layers_0/moe/router",
                  "layers_1/moe/dispatch", "layers_1/moe/experts",
                  "layers_0/moe/combine", "layers_1/moe/zero", "lm_head"):
        assert f'"jit(decode_step)/{scope}' in text, scope
    assert "layers_0/attn/" not in text and "layers_0/mlp" not in text
    tiled = [k for k in eng.step_keys if k != ("decode_step",) and k[0] > 4]
    text = eng.lower_step(tiled[0]).as_text(debug_info=True)
    for scope in ("sub_0/attn/prefill_read", "sub_1/attn/prefill_read",
                  "sub_1/attn/latent_read", "moe/zero"):
        assert f"layers_1/{scope}" in text, scope
    # with the kernels (interpret mode) the expansion has a scope of its own
    eng = _engine(_params(HF_KERNEL), hf=HF_KERNEL, interpret=True,
                  max_seqs=4)
    eng._get_step(4 + TILE, TILE)
    text = eng.lower_step((4 + TILE, TILE)).as_text(debug_info=True)
    for scope in ("attn/expand", "attn/prefill_read", "attn/latent_read"):
        assert f"layers_1/sub_1/{scope}" in text, scope


# ------------------------------------------------------------------ #
# (f) the loader: the published names, the rope dims de-interleaved
# ------------------------------------------------------------------ #
def _interleave(kernel, width, rope):
    """A rotate-half kernel [in, out] -> the published [out, in] weight
    whose rotary dims (the last ``rope`` of every ``width`` outputs) are
    interleaved: the inverse of what the loader does."""
    w = np.asarray(kernel).T
    at = width - rope
    order = np.arange(width)
    half = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    order[at + half] = at + np.arange(rope)
    blocks = w.reshape(-1, width, w.shape[-1])[:, order]
    return blocks.reshape(w.shape)


def test_loader_on_a_synthetic_longcat_flash_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices; ``self_attn.{0,1}``, ``mlps.{0,1}``,
    ``input_layernorm.{0,1}``, ``post_attention_layernorm.{0,1}`` module
    lists; ``mlp.router.classifier`` over experts then zero outputs and its
    ``e_score_correction_bias``; one ``mlp.experts.<n>`` module an expert
    and none for a zero-compute one; rope dims interleaved): the loaded
    tree is the model's, and the engine built by ``from_hf`` serves the
    reference's logits.  (No LongCat-Flash checkpoint is in the
    repository.)"""
    import json

    from safetensors.numpy import save_file

    from deepspeed_tpu.checkpoint.hf_loader import (config_from_hf,
                                                    load_hf_checkpoint)
    from deepspeed_tpu.inference.v2.model_implementations import HF_MODELS

    hf = {**HF, "n_routed_experts": 8}
    hf.pop("router_experts"), hf.pop("expert_start")
    p = _params(hf, seed=4)
    tensors = {}
    nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]

    def put(name, a):
        tensors[name] = np.ascontiguousarray(np.asarray(a, np.float32))

    put("model.embed_tokens.weight", p["embed_tokens"]["embedding"])
    put("model.norm.weight", p["norm"]["scale"])
    put("lm_head.weight", p["lm_head"]["kernel"].T)
    for i in range(hf["num_layers"]):
        lp, pre = p[f"layers_{i}"], f"model.layers.{i}."
        for j in (0, 1):
            sp, att = lp[f"sub_{j}"], lp[f"sub_{j}"]["self_attn"]
            for norm in ("input_layernorm", "post_attention_layernorm"):
                put(f"{pre}{norm}.{j}.weight", sp[norm]["scale"])
            at = f"{pre}self_attn.{j}."
            put(at + "q_a_proj.weight", att["q_a_proj"]["kernel"].T)
            put(at + "q_a_layernorm.weight", att["q_a_layernorm"]["scale"])
            put(at + "q_b_proj.weight", _interleave(
                att["q_b_proj"]["kernel"], nope + rope, rope))
            put(at + "kv_a_proj_with_mqa.weight", _interleave(
                att["kv_a_proj_with_mqa"]["kernel"],
                hf["kv_lora_rank"] + rope, rope))
            put(at + "kv_a_layernorm.weight", att["kv_a_layernorm"]["scale"])
            put(at + "kv_b_proj.weight", att["kv_b_proj"]["kernel"].T)
            put(at + "o_proj.weight", att["o_proj"]["kernel"].T)
            for proj in ("gate_proj", "up_proj", "down_proj"):
                put(f"{pre}mlps.{j}.{proj}.weight",
                    sp["mlp"][proj]["kernel"].T)
        moe = lp["mlp"]
        put(pre + "mlp.router.classifier.weight",
            moe["gate"]["wg"]["kernel"].T)
        put(pre + "mlp.router.e_score_correction_bias",
            moe["gate"]["e_score_correction_bias"])
        for e in range(8):
            for proj, leaf in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                               ("down_proj", "w_down")):
                put(f"{pre}mlp.experts.{e}.{proj}.weight",
                    moe["experts"][leaf][e].T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(
        {**hf, "architectures": ["LongcatFlashForCausalLM"]}))

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "longcat_flash" and HF_MODELS[arch][0] \
        is rl.RaggedLongcatFlash
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.zero_expert_num,
            cfg.moe_topk, cfg.held_experts) == (2, 8, ZERO, 3, None)
    loaded = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert set(flat_w) == set(flat_g)
    for path, a in flat_w.items():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(flat_g[path]),
                                      err_msg=str(path))
    assert loaded["layers_1"]["mlp"]["experts"]["w_down"].shape \
        == (8, 32, 64)
    eng = InferenceEngineV2.from_hf(
        str(tmp_path), dtype=jnp.float32, config=_engine(p, hf=hf).config)
    eng.PREFILL_TILE = TILE
    ids = _ids(66)
    got = _serve(eng, ids, 60)
    ref = family.reference_params(loaded)
    # the loader applies no seeded-bias mapping: the reference reads the
    # bias as the checkpoint has it
    for i, layer in enumerate(ref["layers"]):
        layer["bias"] = loaded[f"layers_{i}"]["mlp"]["gate"][
            "e_score_correction_bias"]
    assert _gap(got, reference.logits_at(
        ref, ids, hf, rows=list(range(59, 66)))) <= F32_TOL
    with pytest.raises(Exception, match="tied head|rope_scaling"):
        (tmp_path / "config.json").write_text(json.dumps(
            {**hf, "tie_word_embeddings": True}))
        config_from_hf(str(tmp_path), jnp.float32)


# ------------------------------------------------------------------ #
# (g) the three latent kernels at the published widths and 64 heads:
# lowered for the TPU from here (Mosaic's own checks; nothing runs)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("which", ["decode", "expand_prefill"])
def test_latent_kernels_lower_for_the_tpu_at_sixty_four_heads(which):
    from deepspeed_tpu.inference.v2.kernels import latent_flash as lf

    h, rank, nope, rope, vd, bs = 64, 512, 128, 64, 128, 128
    width = lf.latent_row_width(rank, rope)
    assert width == 640 and lf.latent_kernels_usable(rank, nope, vd, bs)
    s, b, blocks, tile = 64, 32, 1024, 128
    bf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    pool, tables = bf(blocks * bs, width), ints(s, b)
    if which == "decode":
        fn = lambda q, pool, tables, slot, pos: lf.latent_decode_attention(
            q, pool, tables, slot, pos, block_size=bs, value_dim=rank,
            scale=192 ** -0.5, interpret=False)
        args = (bf(s, h, width), pool, tables, ints(s), ints(s))
        names = ("_latent_decode_kernel",)
    else:
        t = 1024

        def fn(q, pool, w_kvb, tables, slot, pos):
            kv, plan = lf.latent_expand(
                pool, w_kvb, tables, slot, pos, block_size=bs, tile_q=tile,
                rank=rank, interpret=False)
            return lf.latent_prefill_attention(
                q, kv, plan, pos, block_size=bs, tile_q=tile, nope=nope,
                v_dim=vd, scale=192 ** -0.5, interpret=False)
        args = (bf(t, h, nope + 128), pool, bf(rank, h * (nope + vd)),
                tables, ints(t), ints(t))
        names = ("_latent_expand_kernel", "_latent_prefill_kernel")
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    for name in names:
        assert name in text, name
    assert "tpu_custom_call" in text
