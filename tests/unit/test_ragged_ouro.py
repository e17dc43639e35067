"""RaggedOuro (``model_type: ouro``: one stack of layers run several times a
token, a K/V cache per (layer, pass)) against the benchmark's plain float32
reference (``benchmark/reference/ouro.py``: a full forward, no cache), at tiny
sizes on the CPU: 3 layers x 3 passes, so that an off-by-one in either count
shows.

(a) the float32 engine through ``put`` (whole and in chunks) +
``decode_step``, packed back to back and in the two-segment layout, looped
and unrolled, and a bf16 engine on rounded weights; every fault of the chip's
fault table fails the tolerance.  (b) a preempted and recomputed request, a
request handed over with its rows, and several requests interleaved through
the scheduler on a pool that preempts.  (c) the exit distribution against the
reference's.  (d) what is not computed is refused by name.  (e) the passes
are ONE loop in a lowered step program, no pool-sized copy beside it, and the
device scopes.  (f) the one-token rows take the decode walk through the moved
tables.  (g) every cache feature the layout serves.  (h) the loader on a
checkpoint under the published tensor names.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _path in (_ROOT, os.path.join(_ROOT, "tools")):
    sys.path.insert(0, _path)

from benchmark.families import ouro as family                 # noqa: E402
from benchmark.reference import ouro as reference             # noqa: E402
from benchmark.tools.calls import pr43_faults                 # noqa: E402
from deepspeed_tpu.inference.v2 import (                      # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.kernels import blocked_flash  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations import (  # noqa: E402
    OuroConfig, RaggedOuro)
from deepspeed_tpu.inference.v2.modules import attention      # noqa: E402
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (  # noqa: E402
    unpack_metadata)

BS, LAYERS, PASSES = 16, 3, 3
HF = {"model_type": "ouro", "vocab_size": 256, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": LAYERS,
      "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
      "total_ut_steps": PASSES, "early_exit_threshold": 1,
      "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
      "max_position_embeddings": 4096, "sliding_window": None,
      "use_sliding_window": False, "tie_word_embeddings": False}

# float32 engine against the float32 reference, largest |difference| over
# the largest |reference logit|: the same mathematics in another order
F32_TOL = 2e-5
# bf16 engine against the float32 reference on the same bf16-rounded weights
BF16_TOL = 0.05


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


def engine(p, hf=HF, compute=jnp.float32, budget=32, tile=None, blocks=24,
           max_context=256, seqs=4, **kv):
    model = family.serve_model(hf, BS)
    model.config = dataclasses.replace(model.config, dtype=compute)
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


def _serve(eng, tokens, n_prompt, uid=7, chunks=None):
    """Logits after the prompt (fed whole, or in ``chunks``) and after each
    further token through ``decode_step``."""
    if chunks:
        at = 0
        for n in chunks:
            out = eng.put([uid], [tokens[at:at + n].tolist()])
            at += n
        assert at == n_prompt
    else:
        out = eng.put([uid], [tokens[:n_prompt].tolist()])
    got = [np.asarray(out[uid], np.float32)]
    for t in tokens[n_prompt:-1]:
        got.append(np.asarray(eng.decode_step([uid], [int(t)]),
                              np.float32)[0])
    eng.flush([uid])
    return np.stack(got)


def _want(p, tokens, n_prompt, hf=HF):
    return reference.logits_at(
        family.reference_params(p), tokens[:-1], hf,
        rows=list(range(n_prompt - 1, len(tokens) - 1)))


def _gap(got, want) -> float:
    assert got.shape == want.shape and np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ------------------------------------------------------------------ #
# (a) one sequence against the reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("budget, tile, chunks, unrolled", [
    (24, None, None, False), (64, 16, None, False),
    (64, 16, [64, 33, 1, 42], False), (32, 16, [7, 90, 43], False),
    (64, 16, None, True)], ids=[
        "packed_back_to_back", "two_segments", "a_chunk_of_one_row",
        "chunks_over_the_budget", "unrolled"])
def test_f32_engine_matches_reference(budget, tile, chunks, unrolled):
    p, tokens = params(), ids(140 + 10)
    with pr43_faults.fault("unrolled" if unrolled else "clean"):
        eng = engine(p, budget=budget, tile=tile)
        got = _serve(eng, tokens, 140, chunks=chunks)
    sm = eng.state_manager
    assert _gap(got, _want(p, tokens, 140)) <= F32_TOL
    assert sm.allocator.free_blocks == sm.allocator.num_blocks - 1


def test_a_chunked_prompt_equals_the_unchunked_one():
    p, tokens = params(), ids(100 + 4)
    whole = _serve(engine(p, budget=128, tile=16), tokens, 100)
    chunked = _serve(engine(p, budget=32, tile=16), tokens, 100)
    assert _gap(chunked, whole) <= F32_TOL


def test_bf16_engine_is_the_same_model():
    p, tokens = params(), ids(100 + 6)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), p)
    got = _serve(engine(jax.tree.map(lambda a: a.astype(jnp.bfloat16), p),
                        compute=jnp.bfloat16), tokens, 100)
    assert _gap(got, _want(rounded, tokens, 100)) <= BF16_TOL


@pytest.mark.parametrize("fault", pr43_faults.FAULTS)
def test_a_seeded_fault_fails_the_tolerance(fault):
    """The negative cases the chip's check is held to
    (``benchmark/tools/calls/pr43_faults.py`` says what each is), each
    alone."""
    p, tokens = params(), ids(140 + 6)
    want = _want(p, tokens, 140)
    with pr43_faults.fault(fault):
        got = _serve(engine(p, budget=64, tile=16), tokens, 140)
    assert _gap(got, want) > 100 * F32_TOL


def test_the_bf16_stream_reading_is_the_same_model():
    """``bf16_stream`` of the fault table is no fault: in float32 compute it
    changes nothing but the stream's own roundings."""
    p, tokens = params(), ids(60 + 4)
    with pr43_faults.fault("bf16_stream"):
        got = _serve(engine(p), tokens, 60)
    assert F32_TOL < _gap(got, _want(p, tokens, 60)) <= BF16_TOL


# ------------------------------------------------------------------ #
# (b) preemption, handoff, several sequences through the scheduler
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("include_kv", [False, True],
                         ids=["recomputed", "handed_over_with_its_rows"])
def test_a_preempted_request_gives_the_same_logits(include_kv):
    p, tokens = params(), ids(70 + 8)
    eng = engine(p, budget=64, tile=16)
    sm = eng.state_manager
    got = [np.asarray(eng.put([1], [tokens[:70].tolist()])[1], np.float32)]
    for t in tokens[70:74]:
        got.append(np.asarray(eng.decode_step([1], [int(t)]))[0])
    snap = eng.flush_to_host([1], include_kv=include_kv)[1]
    assert sm.allocator.free_blocks == sm.allocator.num_blocks - 1
    # another request takes the freed blocks in between
    eng.put([2], [ids(50, seed=9).tolist()])
    eng.flush([2])
    out = eng.resume(1, tokens[:75].tolist(), kv_state=snap)
    if include_kv:          # 74 positions carried, the 75th token fed
        assert snap["kv"]["layer_0"]["k"].shape[0] == 5 * PASSES * BS
    got.append(np.asarray(out[1], np.float32))
    for t in tokens[75:-1]:
        got.append(np.asarray(eng.decode_step([1], [int(t)]))[0])
    assert _gap(np.stack(got), _want(p, tokens, 70)) <= F32_TOL


def test_interleaved_logits_match_each_reference_on_a_pool_that_preempts():
    """Five requests over three slots and a pool of 14 blocks: chunks of one
    beside decodes of another, joins and leaves, and a preemption by
    recompute; each request's logits are its own reference's."""
    from interleaved_logits import serve_and_compare

    p = params()
    lens, new = (90, 40, 70, 7, 33), (30, 9, 25, 12, 6)
    prompts = [ids(n, seed=10 + i).tolist() for i, n in enumerate(lens)]
    eng = engine(p, seqs=3, blocks=15, budget=64, tile=16)
    out = serve_and_compare(eng, reference, family.reference_params(p), HF,
                            prompts, new)
    assert len(out["gaps"]) == 5 and max(out["gaps"]) <= F32_TOL, out
    sm = eng.state_manager
    assert sm.allocator.free_blocks == sm.allocator.num_blocks - 1


# ------------------------------------------------------------------ #
# (c) the exit gate
# ------------------------------------------------------------------ #
def test_exit_distribution_matches_the_reference_and_sums_to_one():
    """The serving model's own per-pass hidden states (``pass_hiddens``,
    outside any step program) through its gate, against the reference's
    full forward through the reference's."""
    p, tokens = params(), ids(40)
    eng = engine(p, budget=64, tile=16)
    eng._enqueue([5], [tokens.tolist()])
    prepared = eng._build_batch([5])
    batch = unpack_metadata(jnp.asarray(prepared.packed), prepared.bucket,
                            eng._batch.max_seqs, eng._max_blocks)
    logits, _, hiddens = eng.model(
        p, eng.state_manager.kv_cache.cache, batch,
        prefill_tile=prepared.tile, pass_hiddens=True)
    assert hiddens.shape == (PASSES, eng._batch.max_seqs, 64)
    lam, dist = eng.model.exit_distribution(p, hiddens[:, :1])
    ref = family.reference_params(p)
    states = reference.pass_hiddens(ref, tokens, HF)
    assert len(states) == PASSES
    want = reference.exit_distribution(ref, [h[-1:] for h in states])
    np.testing.assert_allclose(np.asarray(lam), want["lambda"], atol=1e-5)
    np.testing.assert_allclose(np.asarray(dist), want["p"], atol=1e-5)
    np.testing.assert_allclose(np.asarray(dist).sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(want["p"].sum(0), 1.0, atol=1e-6)
    # no corner case: every pass has weight, and the last is what is left
    assert (want["p"] > 1e-3).all() and (want["lambda"] < 1).all()
    np.testing.assert_allclose(
        want["p"][-1], np.prod(1 - want["lambda"][:-1], axis=0), atol=1e-6)
    # and the states are those the logits came from
    np.testing.assert_allclose(
        np.asarray(logits[0]),
        reference.logits_at(ref, tokens, HF, rows=[39])[0], atol=2e-4)


# ------------------------------------------------------------------ #
# (d) what is not computed is refused by name
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("key, value, match", [
    ("early_exit_threshold", 0.9, "early_exit_threshold=0.9"),
    ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
    ("sliding_window", 128, "sliding window"),
    ("tie_word_embeddings", True, "tie_word_embeddings")])
def test_what_is_not_computed_is_refused_by_name(key, value, match):
    with pytest.raises(NotImplementedError, match=match):
        family.program_config({**HF, key: value})
    if key != "tie_word_embeddings":
        with pytest.raises(NotImplementedError):
            reference._check({**HF, key: value})


def test_the_model_states_its_passes():
    model = RaggedOuro(OuroConfig(), 128)
    assert (model.num_layers, model.kv_passes) == (48, 4)
    eng = engine(params())
    kv = eng.state_manager.kv_cache
    assert kv.passes == PASSES and eng._cache_layers == LAYERS * PASSES
    assert kv.cache["layer_0"]["k"].shape[0] == PASSES * 24 * BS
    assert kv.per_token_bytes == PASSES * LAYERS * 2 * 4 * 16 * 4


# ------------------------------------------------------------------ #
# (e) the loop in the program's text, and its scopes
# ------------------------------------------------------------------ #
def _programs(eng):
    eng.put([1], [ids(20).tolist()])
    eng.put([1, 2], [[5], ids(30, seed=2).tolist()])
    eng.decode_step([1, 2], [3, 4])
    return eng.step_keys


def test_the_passes_are_one_loop_of_one_stack():
    """A lowered step program holds ONE loop of ``passes`` trips whose body
    is one stack; the unrolled form has ``passes`` stacks in its text and no
    loop."""
    eng = engine(params(), budget=64, tile=16)
    dots = {}
    for key in _programs(eng):
        lowered = eng.lower_step(key)
        text = lowered.as_text()
        assert len(re.findall(r"stablehlo\.while", text)) == 1, key
        dots[key] = len(re.findall(r"stablehlo\.dot_general", text))
        trips = re.findall(r'known_trip_count[^0-9]*(\d+)',
                           lowered.compile().as_text())
        assert trips == [str(PASSES)], (key, trips)
    with pr43_faults.fault("unrolled"):
        flat = engine(params(), budget=64, tile=16)
        for key in _programs(flat):
            text = flat.lower_step(key).as_text()
            assert "stablehlo.while" not in text
            # the head's product once, the stack's ``passes`` times
            assert len(re.findall(r"stablehlo\.dot_general", text)) - 1 \
                == PASSES * (dots[key] - 1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    for name, value in (("TPU_LOG_DIR", "disabled"),
                        ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                        ("TPU_WORKER_HOSTNAMES", "localhost"),
                        ("TPU_SKIP_MDS_QUERY", "true")):
        os.environ.setdefault(name, value)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_chips_compiler_copies_no_pool_in_the_loop(one_chip,
                                                       monkeypatch):
    """``decode_step`` and a two-segment ``put`` program compiled for a
    described v5e at heads of 128 (the cell's pool row, the decode walk and
    the tiled kernel): one loop, the pools its carry, and no ``copy``,
    ``reshape`` or ``transpose`` in the compiled text that writes an array
    of a pool's size."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import packed_length

    hf = {**HF, "head_dim": 128, "num_attention_heads": 2,
          "num_key_value_heads": 2, "num_hidden_layers": 2,
          "hidden_size": 256, "intermediate_size": 512}
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(blocked_flash, "on_tpu", lambda: True)
    sds = lambda a, dt=None: jax.ShapeDtypeStruct(
        a.shape, dt or a.dtype, sharding=one_chip)
    p = jax.tree.map(lambda a: sds(a, jnp.bfloat16),
                     family.serve_param_shapes(hf))
    model = family.serve_model(hf, 128)
    eng = InferenceEngineV2(model, p, RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 256,
                          "max_ragged_sequence_count": 8,
                          "max_context": 512},
        "kv_cache": {"block_size": 128, "num_blocks": 40}}))
    cache = jax.tree.map(sds, eng.state_manager.kv_cache.cache)
    pool = cache["layer_0"]["k"]
    assert pool.shape == (PASSES * 40 * 128, 256) \
        and pool.dtype == jnp.bfloat16
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)
    programs = {
        "decode_step": (eng._get_decode_step(),
                        (ints(8, 4), ints(8), ints(8))),
        "put": (eng._get_step(8 + 128, 128),
                (ints(packed_length(8 + 128, 8, 4, False)),))}
    size = int(np.prod(pool.shape))
    for name, (fn, args) in programs.items():
        text = fn.trace(p, cache, *args).lower().compile().as_text()
        assert len(re.findall(r" while\(", text)) == 1, name
        assert "tpu_custom_call" in text, name
        for m in re.finditer(
                r"= bf16\[([0-9,]+)\]\S* (copy|reshape|transpose)\(", text):
            assert int(np.prod([int(n) for n in m.group(1).split(",")])) \
                != size, (name, m.group(0))


def test_device_scopes_inside_and_outside_the_loop():
    eng = engine(params(), budget=64, tile=16)
    for key in _programs(eng):
        text = eng.lower_step(key).as_text(debug_info=True)
        for scope in ("embed", "loop/layers_0/attn/qkv",
                      "loop/layers_1/attn/rope_insert",
                      "loop/layers_2/attn/out_proj", "loop/layers_2/mlp",
                      "pass_norm", "lm_head"):
            assert scope in text, (key, scope)
        assert "loop/layers_3" not in text and "loop/pass_norm" not in text
    text = eng.lower_step(("decode_step",)).as_text(debug_info=True)
    assert "loop/layers_0/attn/dense_read" in text


def test_the_dispatch_spans_carry_the_passes():
    from deepspeed_tpu.observability.tracer import Tracer

    eng = engine(params())
    trc = Tracer()
    eng.attach_tracer(trc)
    with trc.span("tick"):
        eng.put([1], [ids(9).tolist()])
        eng.decode_step([1], [3])
    spans = {r["name"]: r.get("attrs") or {} for r in trc.records()}
    for name in ("engine/ragged_step", "engine/decode_step"):
        assert spans[name]["passes"] == PASSES
        assert spans[name]["cache_layers"] == PASSES * LAYERS


# ------------------------------------------------------------------ #
# (f) the chip's route in interpret mode
# ------------------------------------------------------------------ #
def test_one_token_rows_take_the_walk_through_the_moved_tables(monkeypatch):
    """At heads of 128 with the chip's route: every one-token read is
    ``_decode_kernel`` and every chunk the tiled kernel, each handed the
    pool of ALL passes and tables moved to the pass's part of it, and the
    logits are the reference's."""
    hf = {**HF, "head_dim": 128, "num_attention_heads": 1,
          "num_key_value_heads": 1, "num_hidden_layers": 2,
          "total_ut_steps": 3, "hidden_size": 32, "intermediate_size": 64}
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    import deepspeed_tpu.inference.v2.kernels as kernels

    calls = {"paged_decode_attention": [], "paged_prefill_attention": []}
    for name, seen in calls.items():
        real = getattr(blocked_flash, name)
        monkeypatch.setattr(kernels, name, lambda *a, _real=real, _seen=seen,
                            **k: (_seen.append(a[1].shape),
                                  _real(*a, **k))[1])
    p, tokens = params(hf), ids(50 + 4)
    eng = engine(p, hf=hf, budget=64, tile=16, blocks=8)
    got = _serve(eng, tokens, 50, chunks=[32, 17, 1])
    assert _gap(got, _want(p, tokens, 50, hf)) <= F32_TOL
    # three programs (two-segment with and without a tile segment, the
    # decode step), the loop's body traced once in each: a walk a layer
    assert calls["paged_decode_attention"] == [(3 * 8 * BS, 128)] * 6
    assert calls["paged_prefill_attention"] == [(3 * 8 * BS, 128)] * 2


# ------------------------------------------------------------------ #
# (g) the cache features the layout serves
# ------------------------------------------------------------------ #
def test_verify_step_and_decode_loop_match_decode_steps():
    p, tokens = params(), ids(40 + 6)
    want = _want(p, tokens, 40)
    eng = engine(p)
    eng.put([1], [tokens[:40].tolist()])
    fed = [int(t) for t in tokens[40:44]]
    got = np.asarray(eng.verify_step([1], [fed]), np.float32)[0]
    assert _gap(got, want[1:5]) <= F32_TOL
    eng.commit_verified(1, fed)
    nxt = np.asarray(eng.decode_step([1], [int(tokens[44])]))[0]
    assert _gap(nxt[None], want[5:6]) <= F32_TOL
    eng.flush([1])
    # the scanned greedy decode is decode_step's tokens
    first = int(np.argmax(np.asarray(eng.put([2], [tokens[:40].tolist()])[2])))
    looped = eng.decode_loop([2], [first], 5)[0]
    eng.flush([2])
    eng.put([3], [tokens[:40].tolist()])
    tok, steps = first, []
    for _ in range(5):
        tok = int(np.argmax(np.asarray(eng.decode_step([3], [tok]))[0]))
        steps.append(tok)
    assert looped.tolist() == steps


def test_int8_pools_hold_every_pass():
    p, tokens = params(), ids(60 + 4)
    eng = engine(p, dtype="int8")
    leaves = eng.state_manager.kv_cache.cache["layer_0"]
    assert leaves["k"].dtype == jnp.int8
    assert leaves["k_scale"].shape == (PASSES * 24 * BS, 4)
    assert _gap(_serve(eng, tokens, 60), _want(p, tokens, 60)) <= 0.05


def test_a_cached_prefix_is_shared_in_every_pass():
    """The second request attaches to the first's blocks (one id names the
    block in every pass), forks the last by copy-on-write, and reads the
    reference's logits; so does a third after the host tier spooled and
    restored them."""
    p = params()
    a = ids(100)
    b = np.concatenate([a[:70], ids(30, seed=8)])
    eng = engine(p, budget=64, tile=16, blocks=12, enable_prefix_cache=True,
                 host_tier=True, host_tier_bytes=1 << 24)
    sm = eng.state_manager
    first = np.asarray(eng.put([1], [a.tolist()])[1], np.float32)
    eng.flush([1])
    ref = family.reference_params(p)
    assert _gap(first[None], reference.logits_at(ref, a, HF, [99])) <= F32_TOL
    got = np.asarray(eng.put([2], [b.tolist()])[2], np.float32)
    assert eng.prefix_cache_stats.hit_tokens == 64
    assert _gap(got[None], reference.logits_at(ref, b, HF, [99])) <= F32_TOL
    eng.flush([2])
    # fill the pool with another prompt: the cached blocks are spooled
    eng.put([3], [ids(150, seed=12).tolist()])
    eng.flush([3])
    assert sm.host_tier.stats.spooled_blocks >= 4
    again = np.asarray(eng.put([4], [a.tolist()])[4], np.float32)
    assert sm.host_tier.stats.restored_blocks >= 1
    assert _gap(again[None], first[None]) <= F32_TOL


# ------------------------------------------------------------------ #
# (h) the loader
# ------------------------------------------------------------------ #
def test_loader_on_a_synthetic_ouro_state_dict(tmp_path):
    """Tensors named and laid out as the published checkpoint has them
    ([out, in] matrices, four norms a layer, ``model.early_exit_gate``):
    the loaded tree is the model's, and the engine serves the reference's
    logits."""
    from safetensors.numpy import save_file

    from deepspeed_tpu.checkpoint.hf_loader import (config_from_hf,
                                                    load_hf_checkpoint)

    p = params(seed=4)
    tensors = {}

    def put(name, a):
        tensors[name] = np.ascontiguousarray(np.asarray(a, np.float32))

    put("model.embed_tokens.weight", p["embed_tokens"]["embedding"])
    put("model.norm.weight", p["norm"]["scale"])
    put("model.early_exit_gate.weight", p["early_exit_gate"]["kernel"].T)
    put("model.early_exit_gate.bias", p["early_exit_gate"]["bias"])
    put("lm_head.weight", p["lm_head"]["kernel"].T)
    for i in range(LAYERS):
        lp, pre = p[f"layers_{i}"], f"model.layers.{i}."
        for norm in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            put(pre + norm + ".weight", lp[norm]["scale"])
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            put(f"{pre}self_attn.{proj}.weight",
                lp["self_attn"][proj]["kernel"].T)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            put(f"{pre}mlp.{proj}.weight", lp["mlp"][proj]["kernel"].T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(
        {**HF, "architectures": ["OuroForCausalLM"]}))

    arch, cfg = config_from_hf(str(tmp_path), jnp.float32)
    assert arch == "ouro" and cfg.total_ut_steps == PASSES
    loaded = load_hf_checkpoint(str(tmp_path), dtype=jnp.float32)
    flat_w = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    flat_g = dict(jax.tree_util.tree_flatten_with_path(loaded)[0])
    assert set(flat_w) == set(flat_g)
    for path, a in flat_w.items():
        np.testing.assert_array_equal(np.asarray(a), np.asarray(flat_g[path]),
                                      err_msg=str(path))
    eng = InferenceEngineV2.from_hf(str(tmp_path), dtype=jnp.float32,
                                    config=engine(p).config)
    assert isinstance(eng.model, RaggedOuro)
    tokens = ids(60 + 5)
    assert _gap(_serve(eng, tokens, 60), _want(loaded, tokens, 60)) <= F32_TOL
    (tmp_path / "config.json").write_text(json.dumps(
        {**HF, "early_exit_threshold": 0.5}))
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        config_from_hf(str(tmp_path), jnp.float32)
