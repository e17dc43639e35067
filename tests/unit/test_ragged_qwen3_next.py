"""Qwen3-Next (``model_type: qwen3_next``) through the normal serving path at
a small size on the CPU: ``RaggedQwen3Next`` -> ``InferenceEngineV2``
(``put``, ``decode_step``, two-segment batches, the state slot pool) ->
``ContinuousBatchScheduler``, against the benchmark's plain float32
reference (``benchmark/reference/qwen3_next.py``: the token-by-token
recurrence; there is one copy, the benchmark's).

Everything that makes the model what it is is drawn away from its neutral
value so that leaving it out fails: zero-centred norm weights uniform in
-0.5 .. 0.5, ``A_log`` and ``dt_bias`` such that a head keeps 40-95% of its
state a token (a dropped carry or reset moves the logits by their scale),
the router N(0, 4/H), a share of 4 of 8 experts from id 2.
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

from benchmark.families import qwen3_next as family         # noqa: E402
from benchmark.reference import qwen3_next as reference     # noqa: E402
from deepspeed_tpu.inference.v2 import (                     # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    ragged_qwen3_next as rq)
from deepspeed_tpu.inference.v2.modules.moe import dropless_moe  # noqa: E402
from deepspeed_tpu.inference.v2.ragged import CacheLayoutError  # noqa: E402
from deepspeed_tpu.ops import gated_delta_rule as gdr        # noqa: E402
from deepspeed_tpu.serving import (ContinuousBatchScheduler,  # noqa: E402
                                   RequestState, SamplingParams)

# the published keys at the test's size: what the reference and the family
# adapter read
HF = {"model_type": "qwen3_next", "vocab_size": 256, "hidden_size": 64,
      "num_hidden_layers": 4, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16,
      "partial_rotary_factor": 0.25, "rope_theta": 10000,
      "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
      "full_attention_interval": 4, "linear_num_key_heads": 2,
      "linear_num_value_heads": 4, "linear_key_head_dim": 16,
      "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
      "num_experts": 4, "router_experts": 8, "expert_start": 2,
      "num_experts_per_tok": 3, "moe_intermediate_size": 32,
      "shared_expert_intermediate_size": 32, "norm_topk_prob": True,
      "rope_scaling": None}
MAX_SEQS, BUDGET, TILE, BLOCK = 4, 64, 16, 8

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|.  Both compute the same float32 mathematics
# in another order (the chunked WY form through flat ragged rows and slots
# against one token after another): the gap is rounding, measured 4e-7 ..
# 7e-7 here.  1e-4 is ~150x that and far below what a dropped carry moves
# the logits by (the negative case below: 0.05 or more).
F32_TOL = 1e-4
# bf16 engine (weights, activations, KV pool, convolution tail; the
# recurrent state stays float32) against the float32 reference on the same
# bf16-rounded weights: bf16 activation roundings and the routings they
# flip.  Measured here over four seeds: 0.0079 .. 0.0110.  0.03 is the
# benchmark's own limit for a bf16 engine (``LOGIT_TOL`` of
# ``runners/serve_ragged.py``) and about three times the measured gap; a
# wrong slot or a dropped carry reads 0.05 or more even in float32.
BF16_TOL = 0.03


def _config(dtype, hf=HF):
    cfg = family.program_config(hf)
    cfg.dtype = dtype
    return cfg


def _params(hf=HF, seed=0):
    rng = np.random.default_rng(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        rq.param_shapes(_config(jnp.float32, hf)))
    out = []
    for path, leaf in flat:
        names = [str(getattr(p, "key", p)) for p in path]
        shape = leaf.shape
        if names[-1] == "scale":
            a = rng.uniform(-0.5, 0.5, shape)
        elif names[-1] in ("A_log", "dt_bias"):
            a = rng.uniform(-3.0, 0.0, shape)
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
    seeded-decay mapping is the benchmark's, undone here)."""
    ref = family.reference_params(params)
    for lp in ref["layers"]:
        if "dt_bias" in lp:
            lp["dt_bias"] = (lp["dt_bias"] - family.DT_SHIFT) \
                / family.DT_SCALE
    return ref


def _engine(params, dtype=jnp.float32, hf=HF, interpret=None, blocks=80,
            max_context=256, **kv):
    model = rq.RaggedQwen3Next(_config(dtype, hf), BLOCK)
    model.interpret = interpret
    eng = InferenceEngineV2(
        model, jax.tree.map(lambda a: a.astype(dtype), params),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": BUDGET,
                              "max_ragged_sequence_count": MAX_SEQS,
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


def _slots(eng):
    """Every live slot's arrays on the host (the scratch slot left out)."""
    pool = eng.state_manager.state_pool
    return {k: {n: np.asarray(a)[:pool.num_slots] for n, a in v.items()}
            for k, v in eng.state_manager.kv_cache.cache.items()
            if "state" in v}


# ------------------------------------------------------------------ #
# (a) one prompt in 1, 2 and 5 chunks, then 6 decode steps
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("n_prompt, interpret", [
    (40, None), (100, None), (270 - 6, None), (100, True)],
    ids=["1_chunk", "2_chunks", "5_chunks", "2_chunks_kernels_interpreted"])
def test_f32_engine_matches_reference(n_prompt, interpret):
    params, ids = _params(), _ids(n_prompt + 6)
    eng = _engine(params, interpret=interpret, blocks=40, max_context=288)
    assert _gap(_serve(eng, ids, n_prompt),
                _want(params, ids, n_prompt)) <= F32_TOL
    assert eng.state_manager.state_pool.held == 0


def test_bf16_engine_is_the_same_model():
    params, ids = _params(), _ids(100 + 6)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = _serve(_engine(params, jnp.bfloat16), ids, 100)
    assert _gap(got, _want(rounded, ids, 100)) <= BF16_TOL


def test_a_dropped_carry_fails_the_tolerance(monkeypatch):
    """The negative case: the same forward with the state zeroed at every
    chunk (what a lost slot or a spurious reset does)."""
    real = gdr.gdn_chunk_reference

    def forgetful(pool, q, k, v, g, beta, slot, reset, tile, chunk=64):
        return real(pool, q, k, v, g, beta, slot, jnp.ones_like(reset),
                    tile, chunk)

    monkeypatch.setattr(gdr, "gdn_chunk_reference", forgetful)
    params, ids = _params(), _ids(100 + 6)
    assert _gap(_serve(_engine(params), ids, 100),
                _want(params, ids, 100)) > 100 * F32_TOL


# ------------------------------------------------------------------ #
# (b) four sequences interleaved through the scheduler, joins and leaves,
# a freed slot reused
# ------------------------------------------------------------------ #
def _greedy(n):
    return SamplingParams(greedy=True, max_new_tokens=n)


def _solo(params, prompt, n_new):
    sched = ContinuousBatchScheduler(_engine(params))
    req = sched.submit(list(prompt), _greedy(n_new))
    sched.run_until_idle()
    return list(req.generated)


PROMPT_LENS, NEW = (150, 40, 90, 7, 33, 70), (4, 9, 5, 12, 6, 5)


@pytest.fixture(scope="module")
def solo_runs():
    params = _params()
    prompts = [_ids(n, seed=10 + i).tolist()
               for i, n in enumerate(PROMPT_LENS)]
    return params, prompts, [_solo(params, p, n)
                             for p, n in zip(prompts, NEW)]


def test_interleaved_sequences_equal_their_solo_runs(solo_runs):
    """Six requests over four slots: the later ones join as earlier ones
    leave and take their slots, which start from zero (the device resets a
    chunk that starts at position 0; a slot is never cleared on release)."""
    params, prompts, want = solo_runs
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs, taken = [], set()
    for i, (p, n) in enumerate(zip(prompts, NEW)):
        reqs.append(sched.submit(p, _greedy(n)))
        sched.step()
        taken |= {s.state_slot for s in eng.state_manager._seqs.values()}
    sched.run_until_idle()
    assert [list(r.generated) for r in reqs] == want
    assert taken <= set(range(MAX_SEQS)) and len(reqs) > MAX_SEQS
    pool = eng.state_manager.state_pool
    assert pool.held == 0 and pool.free == MAX_SEQS
    assert eng.occupancy()["observability/state_slots_held"] == 0.0


def test_rows_that_go_on_keep_their_state_slots(solo_runs):
    """Three requests decode and end by length one after the other.  The
    tick that returns a row's last token sends the next decode step ahead
    over the rows that go on, in a new order: each row's state slot goes
    with it (the slots the device holds for that step are its uids' own,
    the ones the step before held at the rows the tokens are gathered
    from), and every request ends with the tokens of its solo run."""
    params, prompts, want = solo_runs
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    real, gathered = eng.decode_step, []

    def slots():
        return np.asarray(eng._dev_decode_state["slots"][0])

    def decode_step(uids, tokens, greedy=False, rows=None):
        before = slots() if rows is not None else None
        out = real(uids, tokens, greedy=greedy, rows=rows)
        if rows is not None:
            mine = [eng.state_manager.get_sequence(u).state_slot
                    for u in uids]
            assert slots()[:len(uids)].tolist() == mine == \
                before[list(rows)].tolist()
            gathered.append((list(uids), list(rows)))
        return out

    eng.decode_step = decode_step
    picked = (4, 3, 1)                      # 6, 12 and 9 new tokens
    reqs = [sched.submit(prompts[i], _greedy(NEW[i])) for i in picked]
    sched.run_until_idle()
    assert [list(r.generated) for r in reqs] == [want[i] for i in picked]
    # the first row ended first: the two behind it moved up (the row that
    # is left when the next one ends stands where it stood: no gather)
    assert gathered == [([reqs[1].uid, reqs[2].uid], [1, 2])]
    assert eng.state_manager.state_pool.held == 0


def test_interleaved_logits_match_each_reference(solo_runs):
    from interleaved_logits import serve_and_compare

    params, prompts, _ = solo_runs
    out = serve_and_compare(_engine(params), reference, _ref_params(params),
                            HF, prompts[:4], NEW[:4])
    assert max(out["gaps"]) <= F32_TOL, out


# ------------------------------------------------------------------ #
# (c) pad rows and a tile's padded tail change no slot
# ------------------------------------------------------------------ #
def test_pad_rows_and_padded_tails_change_no_other_slot():
    params = _params()
    eng = _engine(params)
    eng.put([1], [_ids(30, seed=1).tolist()])
    eng.put([2], [_ids(50, seed=2).tolist()])
    s1, s2 = (eng.state_manager.get_sequence(u).state_slot for u in (1, 2))
    before = _slots(eng)
    # a decode step of sequence 1 alone: three pad rows
    eng.decode_step([1], [5])
    # a 21-token chunk of a third sequence: a tile and a tile with 11 pad
    # rows, beside no one
    eng.put([3], [_ids(21, seed=3).tolist()])
    s3 = eng.state_manager.get_sequence(3).state_slot
    after = _slots(eng)
    for layer, leaves in before.items():
        for name, a in leaves.items():
            b = after[layer][name]
            assert np.array_equal(a[s2], b[s2]), (layer, name)  # bitwise
            assert not np.array_equal(a[s1], b[s1])
            untouched = [s for s in range(MAX_SEQS) if s not in (s1, s3)]
            assert np.array_equal(a[untouched], b[untouched])


# ------------------------------------------------------------------ #
# (d) preemption by recompute and a forced _abandon
# ------------------------------------------------------------------ #
def test_preemption_by_recompute_gives_the_same_tokens(solo_runs):
    params, prompts, want = solo_runs
    # 14 usable blocks of 8 tokens: the four requests together outgrow
    # them while decoding, so the newest is preempted and recomputed
    eng = _engine(params, blocks=24)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(p, _greedy(n))
            for p, n in zip(prompts[1:5], (30, 25, 40, 30))]
    sched.run_until_idle()
    assert sched.metrics.preemptions >= 1
    solo = [_solo(params, p, n)
            for p, n in zip(prompts[1:5], (30, 25, 40, 30))]
    assert [list(r.generated) for r in reqs] == solo
    assert eng.state_manager.state_pool.held == 0


def test_abandoned_step_recomputes_from_a_zeroed_slot(solo_runs,
                                                      monkeypatch):
    """The fetch of a step dispatched ahead fails: the state it and the
    step behind it wrote is not what the requests were handed, and cannot
    be rolled back; the rows restart from zeroed slots and end with the
    tokens of an undisturbed run."""
    params, prompts, want = solo_runs
    eng = _engine(params)
    sched = ContinuousBatchScheduler(eng)
    reqs = [sched.submit(prompts[i], _greedy(NEW[i])) for i in (1, 3)]
    while sched._inflight is None:
        sched.step()
    real_fetch = sched._fetch

    def failing(arr, launch):
        monkeypatch.setattr(sched, "_fetch", real_fetch)
        raise RuntimeError("device lost")

    monkeypatch.setattr(sched, "_fetch", failing)
    with pytest.raises(RuntimeError, match="device lost"):
        sched.step()
    assert sched._inflight is None
    assert all(r.state is RequestState.PREEMPTED for r in reqs)
    assert eng.state_manager.state_pool.held == 0
    sched.run_until_idle()
    assert [list(r.generated) for r in reqs] == [want[1], want[3]]


# ------------------------------------------------------------------ #
# (e) the share test: the shares' parts add up to the uncut layer
# ------------------------------------------------------------------ #
def test_expert_shares_add_up_to_the_uncut_layer():
    """16 experts at top-4 in 4 shares: the four shares' routed parts plus
    the shared expert counted once equal the uncut reference layer."""
    rng = np.random.default_rng(4)
    h, f, e, k, t = 64, 32, 16, 4, 50
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x = f32(t, h)
    router = 2.0 * f32(h, e) * h ** -0.5
    w_gate, w_up = f32(e, h, f) * h ** -0.5, f32(e, h, f) * h ** -0.5
    w_down = f32(e, f, h) * f ** -0.5
    shared = {"shared_expert": {
        "gate_proj": {"kernel": f32(h, f) * h ** -0.5},
        "up_proj": {"kernel": f32(h, f) * h ** -0.5},
        "down_proj": {"kernel": f32(f, h) * f ** -0.5}},
        "shared_expert_gate": {"kernel": f32(h, 1) * h ** -0.5}}

    def share(start, count, with_shared):
        moe = {"gate": {"wg": {"kernel": router}},
               "experts": {"w_gate": w_gate[start:start + count],
                           "w_up": w_up[start:start + count],
                           "w_down": w_down[start:start + count]},
               **(shared if with_shared else {})}
        return np.asarray(dropless_moe(x, moe, k, jnp.float32,
                                       expert_start=start))

    parts = [share(4 * s, 4, with_shared=(s == 0)) for s in range(4)]
    se = shared["shared_expert"]
    lp = {"router": router, "w_gate": w_gate, "w_up": w_up, "w_down": w_down,
          "s_gate": se["gate_proj"]["kernel"],
          "s_up": se["up_proj"]["kernel"],
          "s_down": se["down_proj"]["kernel"],
          "s_sg": shared["shared_expert_gate"]["kernel"]}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference._moe(x, lp, top_k=k, norm_topk=True,
                                         expert_start=0))
    assert np.max(np.abs(sum(parts) - want)) <= 1e-5 * np.max(np.abs(want))
    # a share alone is a part, not the whole: most rows are routed elsewhere
    assert np.max(np.abs(parts[1] - want)) > 0.1 * np.max(np.abs(want))
    # every expert held: the path OLMoE takes, no share logic
    whole = np.asarray(dropless_moe(
        x, {"gate": {"wg": {"kernel": router}},
            "experts": {"w_gate": w_gate, "w_up": w_up, "w_down": w_down},
            **shared}, k, jnp.float32))
    assert np.max(np.abs(whole - want)) <= 1e-5 * np.max(np.abs(want))


# ------------------------------------------------------------------ #
# (f) the paths that skip or rewind positions refuse by name
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("path", [
    "prefix_cache", "host_tier", "verify_step", "decode_loop",
    "flush_to_host_kv", "resume_kv", "speculative", "untiled_budget"])
def test_paths_that_cannot_carry_state_refuse_by_name(path):
    params = _params()
    if path in ("prefix_cache", "host_tier"):
        kv = {"enable_prefix_cache": True}
        if path == "host_tier":
            kv.update(host_tier=True, host_tier_bytes=1 << 20)
        # attach_prefix, its copy-on-write fork and the host tier all hang
        # off the prefix cache: refused when the engine is built
        with pytest.raises(CacheLayoutError, match="enable_prefix_cache"):
            _engine(params, **kv)
        return
    eng = _engine(params)
    if path == "speculative":
        from deepspeed_tpu.serving import SpeculativeConfig

        with pytest.raises(CacheLayoutError, match="verify_step"):
            ContinuousBatchScheduler(eng, speculative=SpeculativeConfig())
        return
    if path == "untiled_budget":
        eng.PREFILL_TILE = 48           # 64 is no whole number of tiles
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
    # the sequence is as it was: recompute paths still work
    assert eng.state_manager.get_sequence(1).seen_tokens == 20
    assert eng.flush_to_host([1])[1]["seen_tokens"] == 20
    assert eng.generate([_ids(12).tolist()], max_new_tokens=3)[0].shape == (3,)


# ------------------------------------------------------------------ #
# (g) each kernel against its composition, interpret mode, a ragged batch
# whose tiles belong to three sequences
# ------------------------------------------------------------------ #
def _rule_inputs(rows, h=4, dk=16, dv=16, slots=5, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda y: y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)
    f = lambda a: jnp.asarray(a, jnp.float32)
    return (f(rng.standard_normal((slots + 1, h, dk, dv))),
            f(unit(rng.standard_normal((rows, h, dk))) * dk ** -0.5),
            f(unit(rng.standard_normal((rows, h, dk)) + 0.5)),
            f(rng.standard_normal((rows, h, dv))),
            f(-0.3 * np.abs(rng.standard_normal((rows, h)))),
            f(1 / (1 + np.exp(-rng.standard_normal((rows, h))))))


def _token_by_token(pool, q, k, v, g, beta, row_slot, row_reset):
    pool, q, k, v, g, beta = (np.asarray(a, np.float64)
                              for a in (pool, q, k, v, g, beta))
    o = np.zeros(v.shape)
    for t in range(q.shape[0]):
        s = row_slot[t]
        if row_reset[t]:
            pool[s] = 0
        st = pool[s] * np.exp(g[t])[:, None, None]
        d = (v[t] - np.einsum("hkv,hk->hv", st, k[t])) * beta[t][:, None]
        st = st + k[t][:, :, None] * d[:, None, :]
        o[t] = np.einsum("hkv,hk->hv", st, q[t])
        pool[s] = st
    return o, pool


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["composition", "kernel_interpreted"])
def test_chunked_rule_over_tiles_of_three_sequences(interpret):
    tile, n = 16, 6
    pool, q, k, v, g, beta = _rule_inputs(tile * n)
    # sequence A: tiles 0-1 (from position 0), B: tiles 2-4 with a tail of
    # 5 pad rows (continues), C: tile 5 with 9 pad rows (from position 0)
    tile_slot = np.array([2, 2, 0, 0, 0, 4], np.int32)
    tile_reset = np.array([1, 0, 0, 0, 0, 1], bool)
    real = np.ones(tile * n, bool)
    real[5 * tile - 5:5 * tile] = False
    real[6 * tile - 9:] = False
    g = jnp.where(real[:, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    got_o, got_pool = gdr.gdn_chunk(pool, q, k, v, g, beta,
                                    jnp.asarray(tile_slot),
                                    jnp.asarray(tile_reset), tile,
                                    interpret=interpret)
    row_reset = np.zeros(tile * n, bool)
    row_reset[[0, 5 * tile]] = True
    want_o, want_pool = _token_by_token(pool, q, k, v, g, beta,
                                        np.repeat(tile_slot, tile),
                                        row_reset)
    scale = np.max(np.abs(want_o))
    assert np.max(np.abs(np.asarray(got_o)[real] - want_o[real])) \
        <= 1e-5 * scale
    assert np.max(np.abs(np.asarray(got_pool)[:5] - want_pool[:5])) \
        <= 1e-5 * np.max(np.abs(want_pool))
    # slots no tile names are bitwise as they were
    for s in (1, 3):
        assert np.array_equal(np.asarray(got_pool)[s], np.asarray(pool)[s])


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["composition", "kernel_interpreted"])
def test_decode_update_with_pad_rows(interpret):
    pool, q, k, v, g, beta = _rule_inputs(6, seed=1)
    slots = np.array([1, 3, 5, 0, 5, 5], np.int32)     # 5 = scratch
    reset = np.array([0, 1, 1, 0, 1, 1], bool)
    got_o, got_pool = gdr.gdn_step(pool, q, k, v, g, beta,
                                   jnp.asarray(slots), jnp.asarray(reset),
                                   interpret=interpret)
    want_o, want_pool = _token_by_token(pool, q, k, v, g, beta, slots, reset)
    live = [0, 1, 3]
    assert np.max(np.abs(np.asarray(got_o)[live] - want_o[live])) \
        <= 1e-5 * np.max(np.abs(want_o))
    assert np.max(np.abs(np.asarray(got_pool)[:5] - want_pool[:5])) \
        <= 1e-5 * np.max(np.abs(want_pool))
    for s in (2, 4):
        assert np.array_equal(np.asarray(got_pool)[s], np.asarray(pool)[s])


def test_tri_inverse_is_exact_where_a_neumann_series_cancels():
    """All keys alike: A is the strictly lower matrix of ones, whose powers
    reach 1e17 while the inverse is bidiagonal."""
    c = 64
    a = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    inv = np.asarray(gdr._tri_inverse(a))
    want = np.eye(c) - np.eye(c, k=-1)
    assert np.max(np.abs(inv - want)) <= 1e-6


# ------------------------------------------------------------------ #
# a checkpoint under the published tensor names
# ------------------------------------------------------------------ #
def test_hf_checkpoint_round_trip(tmp_path):
    """A tiny ``Qwen3NextForCausalLM`` saved by transformers, loaded by
    name (the DeltaNet projections regrouped from their per-key-head
    interleaving, ``conv1d.weight`` [C, 1, K] -> [K, C], experts stacked),
    served by ``InferenceEngineV2.from_hf``: the engine, the plain
    reference and the published implementation agree."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    if not hasattr(transformers, "Qwen3NextForCausalLM"):
        pytest.skip("this transformers has no qwen3_next")
    from deepspeed_tpu.checkpoint.hf_loader import load_hf_checkpoint

    hf = {k: v for k, v in HF.items()
          if k not in ("router_experts", "expert_start")}
    hf.update(num_experts=8, intermediate_size=96, decoder_sparse_step=1,
              mlp_only_layers=[], tie_word_embeddings=False,
              hidden_act="silu")
    torch.manual_seed(0)
    hf_cfg = transformers.Qwen3NextConfig(
        **{k: v for k, v in hf.items() if k != "model_type"})
    hf_model = transformers.Qwen3NextForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in hf_model.named_parameters():
            if name.endswith("norm.weight"):
                p.uniform_(-0.5, 0.5)
            elif "A_log" in name or "dt_bias" in name:
                p.uniform_(-3.0, 0.0)
            elif "mlp.gate" in name:
                p.normal_(0.0, 2.0 * hf["hidden_size"] ** -0.5)
            elif p.ndim >= 2:
                p.normal_(0.0, p.shape[-1] ** -0.5)
    hf_cfg.save_pretrained(tmp_path)
    hf_model.save_pretrained(tmp_path, safe_serialization=True)

    params = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    want_shapes = jax.tree.map(lambda a: a.shape,
                               rq.param_shapes(_config(jnp.float32, hf)))
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
