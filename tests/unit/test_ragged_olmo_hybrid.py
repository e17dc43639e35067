"""Olmo-Hybrid (``model_type: olmo_hybrid``) through the normal serving path
at a small size on the CPU: ``RaggedOlmoHybrid`` -> ``InferenceEngineV2``
(``put``, ``decode_step``, two-segment batches, the state slot pool AND the
paged KV pool) -> ``ContinuousBatchScheduler``, against the benchmark's
plain float32 reference (``benchmark/reference/olmo_hybrid.py``: a post-norm
block, the token-by-token recurrence; there is one copy, the benchmark's).

The test's size is of the published SHAPE CLASS: 6 heads (no multiple of 4
or 8) of 24 keys x 48 values (``dk != dv``, neither a multiple of 128),
hidden 96, 8 layers (two periods of 3 linear : 1 full), vocabulary 512.
Everything that makes the model what it is is drawn away from its neutral
value so that leaving it out fails: norm weights uniform in 0.5 .. 1.5 (the
post norms' a quarter of that),
``A_log`` and ``dt_bias`` such that a head keeps 40-95% of its state a
token, write strengths up to 2.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _path in (_REPO, os.path.join(_REPO, "tools"),
              os.path.dirname(os.path.abspath(__file__))):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from olmo_hybrid_faults import FAULTS, fault                 # noqa: E402

from benchmark.families import olmo_hybrid as family        # noqa: E402
from benchmark.reference import olmo_hybrid as reference    # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_olmo_hybrid as ro, ragged_qwen3_next as rq)
from deepspeed_tpu.inference.v2.modules import gdn           # noqa: E402
from deepspeed_tpu.inference.v2.ragged import CacheLayoutError  # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   SamplingParams)

KINDS = ["linear_attention"] * 3 + ["full_attention"]
HF = {"model_type": "olmo_hybrid", "vocab_size": 512, "hidden_size": 96,
      "intermediate_size": 160, "num_hidden_layers": 8,
      "num_attention_heads": 6, "num_key_value_heads": 6,
      "layer_types": KINDS * 2, "linear_num_key_heads": 6,
      "linear_num_value_heads": 6, "linear_key_head_dim": 24,
      "linear_value_head_dim": 48, "linear_conv_kernel_dim": 4,
      "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
      "rope_parameters": {"rope_theta": None},
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "attention_bias": False}
#: the same model at a value width the layout rule stores as head PAIRS
#: (``ops/gated_delta_rule.py::state_leaf_shape``: 64 lanes fill no tile,
#: two heads' 128 do); at 48 the state stays ``[6, 24, 48]``
HF_PAIRS = {**HF, "linear_value_head_dim": 64}
WIDTHS = {"natural": HF, "pairs": HF_PAIRS}
MAX_SEQS, BUDGET, TILE, BLOCK = 4, 64, 16, 8

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same float32 mathematics in another
# order (the chunked WY form, whose unit-lower system has off-diagonal
# entries up to 2 here, through flat ragged rows, slots and the paged pool,
# against one token after another).  Measured here: 5e-7 .. 2e-6.  1e-4 is
# 50 x that and far below what any fault of ``olmo_hybrid_faults`` moves
# the logits by at float32 (0.004 or more).
F32_TOL = 1e-4
# bf16 engine (weights, activations, KV pool, convolution tail; the
# recurrent state stays float32) against the float32 reference on the same
# bf16-rounded weights.  ``LOGIT_TOL`` of ``runners/serve_ragged.py``.
BF16_TOL = 0.03


def _config(dtype, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        ro.param_shapes(_config(jnp.float32, hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            # (a post norm's weight is the size of what its sub-layer adds
            # to the stream: a quarter, as the benchmark seeds it, so that
            # the bf16 case reads roundings and not a chaotic stack)
            a = rng.uniform(0.5, 1.5, shape) * (
                0.25 if names[-2].startswith("post_") else 1.0)
        elif names[-1] in ("A_log", "dt_bias"):
            a = rng.uniform(-3.0, 0.0, shape)
        elif names[-1] == "embedding":
            a = rng.standard_normal(shape)
        else:
            a = rng.standard_normal(shape) * shape[0] ** -0.5
        out.append(jnp.asarray(a, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


def _ref_params(params):
    """The reference's dict of the program's own values (the family's two
    seeding mappings are the benchmark's, undone here)."""
    ref = family.reference_params(params)
    for lp in ref["layers"]:
        if "dt_bias" in lp:
            lp["dt_bias"] = (lp["dt_bias"] - family.DT_SHIFT) \
                / family.DT_SCALE
        lp["post_attn"] = lp["post_attn"] / family.POST_NORM
        lp["post_ff"] = lp["post_ff"] / family.POST_NORM
    return ref


def _engine(params, dtype=jnp.float32, hf=HF, interpret=None, blocks=80,
            max_context=256):
    model = ro.RaggedOlmoHybrid(_config(dtype, hf), BLOCK)
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(dtype), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": MAX_SEQS,
                              "max_context": max_context},
            "kv_cache": {"block_size": BLOCK, "num_blocks": blocks}}))
    eng.PREFILL_TILE = TILE          # a 64-token budget of whole tiles
    return eng


def _ids(n, seed=3):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"],
                                                size=(n,))


def _state_leaf(eng):
    return eng.state_manager.kv_cache.cache["layer_0"]["state"].shape[1:]


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
# (a) one prompt in 1, 2 and 5 chunks, then 6 decode steps, both pools
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, interpret, width", [
    (40, None, "natural"), (100, None, "natural"), (270 - 6, None, "natural"),
    (100, True, "natural"), (100, None, "pairs"), (100, True, "pairs")],
    ids=["1_chunk", "2_chunks", "5_chunks", "2_chunks_kernels_interpreted",
         "2_chunks_pairs", "2_chunks_pairs_kernels_interpreted"])
def test_f32_engine_matches_reference(n_prompt, interpret, width):
    hf = WIDTHS[width]
    params, ids = _params(hf), _ids(n_prompt + 6)
    eng = _engine(params, hf=hf, interpret=interpret, blocks=40,
                  max_context=288)
    assert _state_leaf(eng) == {"natural": (6, 24, 48),
                                "pairs": (3, 24, 128)}[width]
    assert _gap(_serve(eng, ids, n_prompt),
                _want(params, ids, n_prompt, hf)) <= F32_TOL
    assert eng.state_manager.state_pool.held == 0
    assert eng.state_manager.free_blocks == 40 - 1      # (the trash block)


def test_bf16_engine_is_the_same_model():
    params, ids = _params(), _ids(100 + 6)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = _serve(_engine(params, jnp.bfloat16), ids, 100)
    assert _gap(got, _want(rounded, ids, 100)) <= BF16_TOL


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("name", [f for f in FAULTS
                                  if f not in ("state_bf16",
                                               "products_bf16")])
def test_a_fault_fails_the_tolerance(name, width):
    """Each fault of the state and of the mathematics, at float32, against
    the unchanged reference: far over the limit, on either layout of the
    state."""
    hf = WIDTHS[width]
    params, ids = _params(hf), _ids(100 + 6)
    with fault(name):
        gap = _gap(_serve(_engine(params, hf=hf), ids, 100),
                   _want(params, ids, 100, hf))
    assert gap > 30 * F32_TOL, gap


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_a_bf16_state_is_seen_at_float32(width):
    """Small beside a fault of the mathematics, and still over the float32
    limit (``products_bf16`` is the chip's: the CPU multiplies float32 at
    full precision whatever ``precision`` says)."""
    hf = WIDTHS[width]
    params, ids = _params(hf), _ids(100 + 6)
    with fault("state_bf16"):
        gap = _gap(_serve(_engine(params, hf=hf), ids, 100),
                   _want(params, ids, 100, hf))
    assert gap > 3 * F32_TOL, gap


# ------------------------------------------------------------------ #
# (b) sequences interleaved through the scheduler over both pools
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


PROMPT_LENS, NEW = (150, 40, 90, 7, 33, 70), (4, 9, 5, 12, 6, 5)


@pytest.fixture(scope="module")
def solo_runs():
    params = _params()
    prompts = [_ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    want = []
    for p, n in zip(prompts, NEW):
        sched = ContinuousBatchScheduler(_engine(params))
        req = sched.submit(list(p), _greedy(n))
        sched.run_until_idle()
        want.append(list(req.generated))
    return params, prompts, want


def test_interleaved_sequences_equal_their_solo_runs(solo_runs):
    """Six requests over four slots: the later ones join as earlier ones
    leave and take their state slots and their KV blocks."""
    params, prompts, want = solo_runs
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs = []
    for p, n in zip(prompts, NEW):
        reqs.append(sched.submit(p, _greedy(n)))
        sched.step()
    sched.run_until_idle()
    assert [list(r.generated) for r in reqs] == want
    pool = eng.state_manager.state_pool
    assert pool.held == 0 and pool.free == MAX_SEQS
    assert eng.state_manager.free_blocks == 80 - 1


def test_interleaved_logits_match_each_reference(solo_runs):
    from interleaved_logits import serve_and_compare

    params, prompts, _ = solo_runs
    out = serve_and_compare(_engine(params), reference, _ref_params(params),
                            HF, prompts[:4], NEW[:4])
    assert max(out["gaps"]) <= F32_TOL, out


def test_admission_runs_out_of_whichever_pool_is_short(solo_runs):
    """Both pools bind: with four slots and few blocks the KV pool holds a
    request back (it waits, nothing fails); with many blocks the fifth
    request waits for a state slot.  Either way every request ends with
    its solo run's tokens."""
    params, prompts, want = solo_runs
    for blocks in (32, 200):
        eng = _engine(params, blocks=blocks)
        sched = ContinuousBatchScheduler(eng)
        reqs = [sched.submit(p, _greedy(n)) for p, n in zip(prompts, NEW)]
        held = []
        while sched.num_pending:
            sched.step()
            held.append((eng.state_manager.state_pool.held,
                         blocks - 1 - eng.state_manager.free_blocks))
        assert [list(r.generated) for r in reqs] == want
        assert max(s for s, _ in held) <= MAX_SEQS
        assert max(b for _, b in held) <= blocks - 1
        if blocks == 200:
            assert max(s for s, _ in held) == MAX_SEQS


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_pad_rows_and_padded_tails_change_no_other_slot(width):
    params = _params(WIDTHS[width])
    eng = _engine(params, hf=WIDTHS[width])

    def slots():
        pool = eng.state_manager.state_pool
        return {k: {n: np.asarray(a)[:pool.num_slots + 1]
                    for n, a in v.items()}
                for k, v in eng.state_manager.kv_cache.cache.items()
                if "state" in v}

    eng.put([1], [_ids(30, seed=1).tolist()])
    eng.put([2], [_ids(50, seed=2).tolist()])
    s1, s2 = (eng.state_manager.get_sequence(u).state_slot for u in (1, 2))
    before = slots()
    eng.decode_step([1], [5])       # three pad rows beside it
    eng.put([3], [_ids(21, seed=3).tolist()])   # a tile with 11 pad rows
    s3 = eng.state_manager.get_sequence(3).state_slot
    after = slots()
    assert len(before) == 6
    for layer, leaves in before.items():
        for name, a in leaves.items():
            b = after[layer][name]
            assert np.array_equal(a[s2], b[s2]), (layer, name)  # bitwise
            assert not np.array_equal(a[s1], b[s1])
            untouched = [s for s in range(MAX_SEQS) if s not in (s1, s3)]
            assert np.array_equal(a[untouched], b[untouched])


# ------------------------------------------------------------------ #
# (c) what is read from the published keys, and what is refused by name
# ------------------------------------------------------------------ #
def test_everything_is_read_from_published_keys():
    cfg = family.program_config(HF)
    assert cfg.layer_types == tuple(HF["layer_types"])
    assert cfg.head_dim == 16 and cfg.rope_theta is None
    model = ro.RaggedOlmoHybrid(cfg, BLOCK)
    spec = model.state_spec
    assert spec["layers"] == [0, 1, 2, 4, 5, 6]
    assert spec["leaves"]["state"][0] == (6, 24, 48)    # the rule leaves it
    assert spec["leaves"]["conv"][0] == (3 * (2 * 6 * 24 + 6 * 48),)
    pairs = ro.RaggedOlmoHybrid(family.program_config(HF_PAIRS), BLOCK)
    assert pairs.state_spec["leaves"]["state"][0] == (3, 24, 128)
    # the default pattern is the published one
    assert ro.OlmoHybridConfig(num_hidden_layers=8).layer_types == \
        tuple(HF["layer_types"])


def test_bytes_at_the_published_widths():
    """The cell's configuration (shapes only, nothing allocated): a token
    holds 30,720 B of keys and values in the two attention layers; a
    sequence's state is 13,685,760 B as the mathematics counts it and,
    stored as head pairs (``[15, 96, 384]``: three whole lane tiles), as
    the chip holds it and the pool's gauge says; ``[30, 96, 192]`` would
    hold 18,109,440 B (192 lanes stored as 256), as it did before PR 58."""
    import json

    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.ragged.state_pool import (StateSlotPool,
                                                              slot_bytes)

    with open(os.path.join(_REPO, "benchmark", "configs",
                           "olmo-hybrid-7b-serve-1chip.json")) as f:
        hf = json.load(f)
    model = family.serve_model(hf, 128)
    spec = model.state_spec
    pool = StateSlotPool(128, spec["layers"], spec["leaves"])
    shapes = family.shapes(hf)
    assert shapes["state_bytes_per_seq"] == 6 * (2_211_840 + 69_120) \
        == 13_685_760
    assert spec["leaves"]["state"][0] == (15, 96, 384)
    assert slot_bytes((30, 96, 192), jnp.float32) == 2_949_120
    assert slot_bytes((15, 96, 384), jnp.float32) == 2_211_840
    assert pool.per_sequence_bytes == shapes["state_bytes_per_seq"]
    assert pool.total_bytes == 129 * 13_685_760
    kv = BlockedKVCache(10, 1, 128, 30, 128, kv_layers=[3, 7])
    assert kv.cache["layer_3"]["k"].shape == (128, 30 * 128)    # flat
    assert kv.per_token_bytes == 30_720 == shapes["kv_bytes_per_token"]
    assert shapes["total_params"] == 2_435_748_072


@pytest.mark.parametrize("change, error, word", [
    ({"layer_types": ["sliding_attention"] * 8}, NotImplementedError,
     "sliding_attention"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, NotImplementedError,
     "rope_theta"),
    ({"layer_types": KINDS}, ValueError, "num_hidden_layers")])
def test_what_the_model_cannot_serve_is_refused_by_name(change, error, word):
    with pytest.raises(error, match=word):
        family.program_config({**HF, **change})


def test_a_model_axis_is_refused_by_name():
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2),
                ("data", "model"))
    with pytest.raises(ValueError, match="tp = 1"):
        ro.RaggedOlmoHybrid(family.program_config(HF), BLOCK, mesh=mesh)
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
               ("data", "model"))
    ro.RaggedOlmoHybrid(family.program_config(HF), BLOCK, mesh=one)


def test_an_untiled_batch_is_refused():
    params = _params()
    eng = _engine(params)
    eng.PREFILL_TILE = 48            # 64 is no whole number of 48s
    with pytest.raises(CacheLayoutError, match="whole tiles"):
        eng.put([1], [_ids(30).tolist()])
    model = ro.RaggedOlmoHybrid(family.program_config(HF), BLOCK)
    with pytest.raises(CacheLayoutError, match="RaggedOlmoHybrid"):
        model({}, {}, {})


def test_state_features_are_refused_by_the_one_table():
    eng = _engine(_params())
    for feature in ("prefix_cache", "host_tier", "kv_handoff", "verify",
                    "decode_loop"):
        with pytest.raises(CacheLayoutError, match="state"):
            eng.state_manager.require(feature, "test")


def test_both_models_call_the_one_mixer(monkeypatch):
    """``RaggedQwen3Next`` and ``RaggedOlmoHybrid`` go through
    ``modules/gdn.py::gdn_mixer``: the one with the block's input norm
    before it, the other on the raw stream."""
    assert rq.gdn_mixer is gdn.gdn_mixer is ro.gdn_mixer
    seen = []
    real = gdn.gdn_mixer

    def spy(la, xn, *a, **kw):
        seen.append(xn)
        return real(la, xn, *a, **kw)

    monkeypatch.setattr(ro, "gdn_mixer", spy)
    eng = _engine(_params())
    eng.put([1], [_ids(20).tolist()])
    assert len(seen) == 6


def test_hybrid_counters_close_the_dispatch_spans():
    """A model with state slots AND KV layers: ``engine/ragged_step`` and
    ``engine/decode_step`` close with what the launch asked for, from
    lengths the host already has."""
    from deepspeed_tpu.observability.tracer import Tracer

    eng = _engine(_params())
    trc = Tracer()
    eng.attach_tracer(trc)
    eng.put([1], [_ids(40).tolist()])           # 40 tokens from position 0
    eng.decode_step([1], [3])
    eng.put([2, 1], [_ids(20, seed=5).tolist(), [4]])
    by = {}
    for r in trc.records():
        if r["name"] in ("engine/ragged_step", "engine/decode_step"):
            by.setdefault(r["name"], []).append(r["attrs"])
    first, mixed = by["engine/ragged_step"]
    assert (first["hyb_seqs"], first["hyb_tokens"], first["hyb_ctx_tokens"],
            first["hyb_attn_pairs"], first["hyb_state_seqs"]) == \
        (0, 40, 0, 40 * 41 // 2, 1)
    (dec,) = by["engine/decode_step"]
    assert (dec["hyb_seqs"], dec["hyb_tokens"], dec["hyb_ctx_tokens"],
            dec["hyb_attn_pairs"], dec["hyb_state_seqs"]) == (1, 1, 41, 0, 1)
    # sequence 1 feeds one token at position 41, sequence 2 a 20-token chunk
    assert (mixed["hyb_seqs"], mixed["hyb_tokens"], mixed["hyb_ctx_tokens"],
            mixed["hyb_attn_pairs"], mixed["hyb_state_seqs"]) == \
        (1, 21, 42, 20 * 21 // 2, 2)
