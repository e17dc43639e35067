"""Jamba (``model_type: jamba``, dense: ``num_experts`` 1) through the normal
serving path at a small size on the CPU: ``RaggedJamba`` ->
``InferenceEngineV2`` (``put``, ``decode_step``, two-segment batches, the
state slot pool with a float32 scan state beside a convolution tail, a
flat one-KV-head pool row read without positions) ->
``ContinuousBatchScheduler``, against the benchmark's plain float32
reference (``benchmark/reference/jamba.py``: a ``lax.scan`` over the
tokens, a padded convolution, no cache, no state).

Everything that makes the model what it is is drawn away from its neutral
value so that leaving it out fails: norm weights (the inner ones too)
uniform in 0.5 .. 1.5, ``D`` and the convolution's bias N(0, 1), ``A =
-(1..N)`` with ``dt`` about 0.05 (a state that remembers tens of tokens:
chunks here are 16 to 64 tokens long).
"""

import json
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

from benchmark.families import jamba as family              # noqa: E402
from benchmark.reference import jamba as reference          # noqa: E402
from benchmark.tools.calls.pr46_faults import FAULTS, fault  # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_jamba as rj)
from deepspeed_tpu.observability.tracer import Tracer        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)

# the published keys at the test's size: attention at layer 2 of 4
HF = {"model_type": "jamba", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 4,
      "num_attention_heads": 4, "num_key_value_heads": 1,
      "attn_layer_period": 4, "attn_layer_offset": 2,
      "mamba_expand": 2, "mamba_d_state": 4, "mamba_d_conv": 4,
      "mamba_dt_rank": 8, "mamba_conv_bias": True, "mamba_proj_bias": False,
      "num_experts": 1, "num_experts_per_tok": 1, "rms_norm_eps": 1e-6,
      "max_position_embeddings": 512, "tie_word_embeddings": True,
      "sliding_window": None}
MAX_SEQS, BUDGET, TILE, BLOCK = 8, 64, 16, 8

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same float32 mathematics in another
# order (chunks through ragged rows and the slot pool against one scan over
# the sequence), a few 1e-6 here; every fault below reads 100
# times the limit or more.
F32_TOL = 1e-4


def _config(dtype=jnp.float32, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rj.param_shapes(_config()))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape, a = leaf.shape, rng.standard_normal(leaf.shape)
        if names[-1] == "scale":
            a = rng.uniform(0.5, 1.5, shape)
        elif names[-1] == "A_log":          # [N, Di]: A = -(1..N)
            a = np.broadcast_to(np.log(np.arange(1, shape[0] + 1))[:, None],
                                shape)
        elif names[-2:] == ["dt_proj", "bias"]:
            a = -3.0 + 0.5 * a              # dt about 0.05
        elif names[-1] not in ("embedding", "D", "bias"):
            a = a * shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _reference_params(params):
    """The family's mapping without its seeded decay (these weights carry
    their own ``A_log``, ``D`` and ``b_dt``)."""
    old = family._seeded_ssm
    family._seeded_ssm = lambda tree: tree
    try:
        return family.reference_params(params)
    finally:
        family._seeded_ssm = old


def _engine(params, blocks=80, max_context=256, max_seqs=MAX_SEQS,
            interpret=None):
    model = rj.RaggedJamba(_config(), BLOCK)
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, params, RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": max_seqs,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks}}))
    eng.PREFILL_TILE = TILE          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _serve(eng, ids, n_prompt, uid=7, chunks=None):
    """``put`` the prompt (in the given chunk sizes, or as the engine
    splits it), then decode the rest teacher-forced."""
    at = 0
    for n in chunks or [n_prompt]:
        row = eng.put([uid], [ids[at:at + n].tolist()])[uid]
        at += n
    assert at == n_prompt
    got = [np.asarray(row, np.float32)]
    for t in ids[n_prompt:]:
        row = eng.decode_step([uid], [int(t)])
        got.append(np.asarray(jax.device_get(row), np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(params, ids, n_prompt):
    return reference.logits_at(_reference_params(params), ids, HF,
                               rows=list(range(n_prompt - 1, len(ids))))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _states(eng):
    """Every live slot's leaves on the host (the scratch slot left out)."""
    pool = eng.state_manager.state_pool
    return {(k, leaf): np.asarray(a)[:pool.num_slots]
            for k, v in eng.state_manager.kv_cache.cache.items()
            if "ssm" in v for leaf, a in v.items()}


# ------------------------------------------------------------------ #
# (a) one prompt in 1, 2 and 4 chunks, among them a chunk of one row, then
# 5 decode steps; the kernels in interpret mode and the compositions
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, chunks, interpret", [
    (40, None, None), (100, None, None), (100, None, True),
    (66, [64, 1, 1], None), (70, [3, 1, 2, 64], True)],
    ids=["1_chunk", "2_chunks", "2_chunks_kernels", "chunks_of_one_row",
         "chunks_shorter_than_the_taps_kernels"])
def test_f32_engine_matches_reference(n_prompt, chunks, interpret):
    params, ids = _params(), _ids(n_prompt + 5)
    eng = _engine(params, interpret=interpret)
    assert _gap(_serve(eng, ids, n_prompt, chunks=chunks),
                _want(params, ids, n_prompt)) <= F32_TOL
    assert eng.state_manager.state_pool.held == 0


def test_a_chunked_prompt_equals_the_unchunked_one():
    params, ids = _params(), _ids(64 + 3)
    whole = _serve(_engine(params), ids, 64)
    parts = _serve(_engine(params), ids, 64, chunks=[16, 32, 16])
    assert _gap(parts, whole) <= F32_TOL


@pytest.mark.parametrize("name", FAULTS)
def test_a_seeded_fault_fails_the_tolerance(name):
    """The negative cases the chip check is held to
    (``benchmark/tools/calls/pr46_faults.py``): each alone moves the logits
    by 100 times the float32 limit or more.  The sequence's slot held
    another sequence's state before."""
    params, ids = _params(), _ids(100 + 5)
    want = _want(params, ids, 100)
    with fault(name):
        eng = _engine(params, max_seqs=1)
        eng.put([3], [_ids(40, seed=8).tolist()])
        eng.flush([3])
        got = _serve(eng, ids, 100)
    assert _gap(got, want) > 100 * F32_TOL


# ------------------------------------------------------------------ #
# (b) six sequences interleaved through the scheduler: joins, a flush, a
# reused slot, a preemption by recompute
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
    out = serve_and_compare(eng, reference, _reference_params(params), HF,
                            prompts, NEW)
    assert len(out["gaps"]) == 6 and max(out["gaps"]) <= F32_TOL, out
    assert eng.state_manager.state_pool.held == 0


def test_a_reused_slot_starts_from_zero(served):
    """Three slots, six requests: the later ones take slots the earlier
    ones left, whose state is whatever they held (a slot is never cleared
    on release; a chunk that starts at position 0 reads zeros)."""
    from interleaved_logits import serve_and_compare

    params, prompts = served
    eng = _engine(params, max_seqs=3)
    out = serve_and_compare(eng, reference, _reference_params(params), HF,
                            prompts, NEW)
    assert max(out["gaps"]) <= F32_TOL, out
    assert all(np.abs(a).max() > 0 for a in _states(eng).values())
    assert eng.state_manager.state_pool.free == 3


def test_preemption_by_recompute_gives_the_same_logits(served):
    params, prompts = served

    def solo(p, n):
        sched = ContinuousBatchScheduler(_engine(params))
        req = sched.submit(list(p), _greedy(n))
        sched.run_until_idle()
        return list(req.generated)

    news = (30, 25, 40, 30)
    # 23 usable blocks of 8 tokens: the four requests together outgrow
    # them while decoding, so the newest is preempted and recomputed from
    # a zeroed slot
    eng = _engine(params, blocks=24)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts[1:5], news)]
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    assert [list(r.generated) for r in reqs] == [
        solo(p, n) for p, n in zip(prompts[1:5], news)]
    assert eng.state_manager.state_pool.held == 0
    # and the logits of a recomputed sequence are the reference's
    ids = np.asarray(prompts[4] + list(reqs[3].generated))
    got = _serve(eng, ids, len(prompts[4]))
    assert _gap(got, _want(params, ids, len(prompts[4]))) <= F32_TOL


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["compositions", "kernels"])
def test_pad_rows_and_padded_tails_change_no_other_slot(interpret):
    eng = _engine(_params(), max_seqs=4, interpret=interpret)
    eng.put([1], [_ids(30, seed=1).tolist()])
    eng.put([2], [_ids(50, seed=2).tolist()])
    s1, s2 = (eng.state_manager.get_sequence(u).state_slot for u in (1, 2))
    before = _states(eng)
    eng.decode_step([1], [5])       # three pad rows beside it
    eng.put([3], [_ids(21, seed=3).tolist()])   # a tile with 11 pad rows
    s3 = eng.state_manager.get_sequence(3).state_slot
    after = _states(eng)
    for key, a in before.items():
        b = after[key]
        assert np.array_equal(a[s2], b[s2]), key            # bitwise
        assert not np.array_equal(a[s1], b[s1])
        untouched = [s for s in range(4) if s not in (s1, s3)]
        assert np.array_equal(a[untouched], b[untouched])


# ------------------------------------------------------------------ #
# (c) what the state costs, in bytes, where the engine says it
# ------------------------------------------------------------------ #
def test_bytes_a_token_and_a_sequence_hold():
    eng = _engine(_params(), max_seqs=3)
    pool = eng.state_manager.state_pool
    # 3 Mamba layers x (4 x 128 float32 + 3 x 128 float32 at this dtype)
    assert pool.per_sequence_bytes == 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert pool.total_bytes == 4 * pool.per_sequence_bytes
    assert pool.held_bytes == 0
    eng.put([1], [_ids(20).tolist()])
    assert pool.held_bytes == pool.per_sequence_bytes
    from deepspeed_tpu.observability.memory import kv_occupancy

    g = kv_occupancy(eng.state_manager)
    assert g["observability/state_live_bytes"] == pool.per_sequence_bytes
    assert g["observability/state_pool_bytes"] == pool.total_bytes
    cache = eng.state_manager.kv_cache.cache
    assert cache["layer_0"]["ssm"].shape == (4, 4, 128)
    assert cache["layer_0"]["ssm"].dtype == jnp.float32
    assert cache["layer_0"]["conv"].shape == (4, 3 * 128)
    assert set(cache["layer_2"]) == {"k", "v"}
    # one KV head of 16: no whole lane tile, so [rows, Hkv, D]
    assert cache["layer_2"]["k"].shape[1:] == (1, 16)


def test_bytes_at_the_published_widths():
    hf = json.load(open(os.path.join(
        _REPO, "benchmark/configs/jamba2-3b-serve-1chip.json")))
    model = rj.RaggedJamba(family.program_config(hf), 128)
    spec = model.state_spec
    assert spec["layers"] == [i for i in range(28) if i not in (7, 21)]
    assert spec["leaves"] == {"ssm": ((16, 5120), jnp.float32),
                              "conv": ((3 * 5120,), jnp.bfloat16)}
    from deepspeed_tpu.inference.v2.ragged.kv_cache import flat_row
    from deepspeed_tpu.inference.v2.ragged.state_pool import StateSlotPool

    pool = StateSlotPool(256, spec["layers"], spec["leaves"])
    assert pool.per_sequence_bytes == 9_318_400
    assert pool.total_bytes == 257 * 9_318_400
    # the attention layers' row: one lane tile, stored flat
    assert flat_row(jnp.bfloat16, model.num_kv_heads, model.head_dim)
    assert family.shapes(hf)["state_bytes_per_seq"] == 9_318_400


def test_routed_experts_are_refused_by_name():
    with pytest.raises(NotImplementedError, match="num_experts=16"):
        family.program_config({**HF, "num_experts": 16})
    with pytest.raises(ValueError, match="one expert"):
        reference.logits_at({}, _ids(4), {**HF, "num_experts": 16}, [3])


# ------------------------------------------------------------------ #
# (d) the device scopes exist; the byte counters ride the dispatch spans
# ------------------------------------------------------------------ #
def test_device_scopes_of_a_mixed_batch():
    eng = _engine(_params())
    eng.put([1], [_ids(20).tolist()])
    eng.put([1, 2], [[5], _ids(30, seed=2).tolist()])
    text = "\n".join(eng.lower_step(k).as_text(debug_info=True)
                     for k in eng.step_keys)
    for scope in ("layers_0/mamba/in_proj", "layers_0/mamba/conv",
                  "layers_0/mamba/x_proj", "layers_0/mamba/scan",
                  "layers_0/mamba/out", "layers_2/attn/qkv",
                  "layers_2/attn/rope_insert", "layers_2/attn/out_proj",
                  "layers_0/mlp", "layers_2/mlp", "lm_head"):
        assert scope in text, scope
    assert "layers_2/mamba" not in text and "layers_0/attn" not in text


def test_state_bytes_ride_the_dispatch_spans():
    trc = Tracer()
    eng = _engine(_params())
    per_seq = eng.state_manager.state_pool.per_sequence_bytes
    sched = ContinuousBatchScheduler(eng, tracer=trc)
    a = sched.submit(_ids(20).tolist(), _greedy(8))
    while len(a.generated) < 3:
        sched.step()
    sched.submit(_ids(30, seed=2).tolist(), _greedy(2))
    sched.run_until_idle()
    spans = [r["attrs"] for r in trc.records() if r.get("attrs") and
             r["name"] in ("engine/build_batch", "engine/decode_prep")]
    assert {s["state_slots"] for s in spans} == {1, 2}
    for s in spans:
        assert s["state_bytes"] == s["state_slots"] * per_seq
        assert s["state_bytes_total"] == (MAX_SEQS + 1) * per_seq
