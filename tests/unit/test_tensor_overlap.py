"""parallel/tensor_overlap.py: the ring helpers against the plain product
and ``psum``, the rule for when the ring engages, and the counter."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.models.llama import init_kv_cache
from deepspeed_tpu.parallel import groups, tensor_overlap as to

BATCH = ("dout", "data", "expert")


@pytest.fixture(autouse=True)
def _no_topology_left():
    yield
    groups.reset()


def _mesh(dp, tp):
    groups.reset()
    return groups.initialize_mesh(
        model_parallel_size=tp, data_parallel_size=dp,
        devices=jax.devices()[:dp * tp]).mesh


def _inputs(mesh, dtype, n_gather, b=2, t=None, h=64, f=128, seed=0):
    """x, ``n_gather`` column-parallel weights and one row-parallel weight,
    placed as the engine places them: the batch over ``data``, the weights
    over ``model``; the shortest sequence at which the ring engages."""
    t = t or to.MIN_CHUNK_ROWS * mesh.shape["model"]
    keys = jax.random.split(jax.random.key(seed), n_gather + 2)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    x = jax.device_put(
        jax.random.normal(keys[0], (b, t, h), jnp.float32).astype(dtype),
        sh(BATCH))
    cols = {f"w{i}": jax.device_put(
        (jax.random.normal(keys[1 + i], (h, f), jnp.float32) * h ** -0.5
         ).astype(dtype), sh(None, "model")) for i in range(n_gather)}
    row = jax.device_put(
        (jax.random.normal(keys[-1], (f, h), jnp.float32) * f ** -0.5
         ).astype(dtype), sh("model", None))
    return x, cols, row


def _psum_reference(x, cols, row):
    """The sublayer as the partition rules alone state it: whole tokens a
    rank, a product by the rank's columns, a product by its rows and the
    all-reduce GSPMD puts after it."""
    return jnp.dot(jnp.sin(sum(jnp.dot(x, w) for w in cols.values())), row)


def _ring_sublayer(ring, x, cols, row):
    outs = to.gather_column_parallel(ring, ring.shard_tokens(x), cols)
    return to.row_parallel_scatter(
        ring, jnp.sin(sum(outs.values())), row, name="row")


def _loss(f):
    return lambda *a: jnp.sum(jnp.cos(f(*a).astype(jnp.float32)))


def _assert_close(ref, got, tol):
    """Leaf by leaf, to ``tol`` of the reference leaf's largest value."""
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        scale = float(jnp.max(jnp.abs(a.astype(jnp.float32)))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(b, np.float32) / scale,
            np.asarray(a, np.float32) / scale, atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("n_gather", [1, 3])
@pytest.mark.parametrize("dp,tp", [(2, 2), (2, 4), (1, 4)])
def test_helpers_match_the_product_and_psum(dp, tp, n_gather, dtype, tol):
    """Values and every gradient of gather -> elementwise -> scatter agree
    with the plain products and a ``psum``; the result is token-sharded
    over 'model' and the batch stays over 'data'."""
    mesh = _mesh(dp, tp)
    x, cols, row = _inputs(mesh, dtype, n_gather)
    ring = to.plan(x.shape[1], sites=2)
    assert ring == to.Ring(mesh, tp)
    ref = jax.jit(jax.value_and_grad(
        _loss(_psum_reference),
        argnums=(0, 1, 2)))(x, cols, row)
    with to.recording() as sites:
        run = jax.jit(jax.value_and_grad(
            _loss(lambda x, c, r: _ring_sublayer(ring, x, c, r)),
            argnums=(0, 1, 2)))
        got = run(x, cols, row)
    assert sites == {"ring": 2, "steps": tp, "fallbacks": {}}
    _assert_close(ref, got, tol)
    y = jax.jit(lambda x, c, r: _ring_sublayer(ring, x, c, r))(x, cols, row)
    assert y.sharding.spec[1] == "model"
    if dp > 1:
        assert "data" in str(y.sharding.spec[0])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("dp,tp", [(2, 2), (1, 4)])
def test_gated_mlp_matches_the_two_helpers(dp, tp, dtype, tol):
    """The sublayer in one manual region (chunks never put together, the
    gate recomputed in the backward pass) gives the helpers' numbers."""
    mesh = _mesh(dp, tp)
    x, cols, row = _inputs(mesh, dtype, 2)
    ring = to.plan(x.shape[1], sites=2)

    def helpers(x, c, r):
        o = to.gather_column_parallel(ring, ring.shard_tokens(x), c)
        return to.row_parallel_scatter(
            ring, nn.silu(o["w0"]) * o["w1"], r, name="row")

    def fused(x, c, r):
        return to.gated_mlp(ring, ring.shard_tokens(x), c["w0"], c["w1"], r,
                            nn.silu)

    ref = jax.jit(jax.value_and_grad(_loss(helpers), argnums=(0, 1, 2)))(
        x, cols, row)
    with to.recording() as sites:
        got = jax.jit(jax.value_and_grad(_loss(fused), argnums=(0, 1, 2)))(
            x, cols, row)
    assert sites["ring"] == 2          # a gather and a scatter
    _assert_close(ref, got, tol)


def _one_gemm(x, dy):
    """``x^T dy`` over every token in float64, rounded to bf16 once: the
    weight gradient of the one GEMM the partition rules alone state."""
    ref = np.einsum("btf,bth->fh", np.asarray(x, np.float64),
                    np.asarray(dy, np.float64))
    return np.asarray(jnp.asarray(ref, jnp.float32).astype(jnp.bfloat16),
                      np.float32)


def _assert_rounded_once(ref, got, what, same_inputs=True):
    """``got`` (bf16) is ``ref`` to one bf16 rounding, and the same number
    nearly everywhere: a float32 accumulation differs from the exact one
    only at a rounding boundary, a sum of bf16 terms in a third of the
    entries.  ``same_inputs=False``: the product's inputs are themselves
    bf16 products, which a chunk and the whole sequence round alike but
    for an entry in thousands, so the bound is one rounding of the leaf's
    largest value."""
    assert got.dtype == jnp.bfloat16, what
    got = np.asarray(got, np.float32)
    at = np.abs(ref) if same_inputs else np.max(np.abs(ref))
    ulp = 2.0 ** (np.floor(np.log2(at + 1e-30)) - 7)
    assert np.all(np.abs(got - ref) <= ulp), what
    assert np.mean(got == ref) > 0.99, (what, np.mean(got == ref))


@pytest.mark.parametrize("tp", [2, 4])
def test_bf16_weight_gradients_are_rounded_once(tp):
    """Every weight gradient of the three helpers is the single GEMM's
    number (accumulated in float32 over all of a rank's tokens, rounded
    to bf16 once), not one more rounding a chunk."""
    mesh = _mesh(1, tp)
    x, cols, row = _inputs(mesh, jnp.bfloat16, 3)
    ring = to.plan(x.shape[1], sites=2)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    keys = jax.random.split(jax.random.key(7), 3)
    b, t, h = x.shape
    f = row.shape[0]
    rand = lambda key, shape, spec: jax.device_put(
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16),
        spec)
    xs = jax.device_put(x, sh(None, "model", None))
    dy_h = rand(keys[0], (b, t, h), sh(None, "model", None))
    wide = rand(keys[1], (b, t, f), sh(None, None, "model"))

    _, back = jax.vjp(lambda a, w: to.row_parallel_scatter(
        ring, a, w, name="row"), wide, row)
    _assert_rounded_once(_one_gemm(wide, dy_h), back(dy_h)[1], "scatter")

    _, back = jax.vjp(lambda a, ws: to.gather_column_parallel(ring, a, ws),
                      xs, cols)
    dys = {name: rand(key, (b, t, f), sh(None, None, "model"))
           for name, key in zip(cols, jax.random.split(keys[2], 3))}
    for name, dw in back(dys)[1].items():
        _assert_rounded_once(_one_gemm(x, dys[name]), dw, name)

    wg, wu = cols["w0"], cols["w1"]
    _, back = jax.vjp(lambda a, wg, wu, wd: to.gated_mlp(
        ring, a, wg, wu, wd, nn.silu), xs, wg, wu, row)
    _dx, dwg, dwu, dwd = back(dy_h)
    # the chain on whole arrays, one device, the helper's bf16 steps
    host = lambda a: jnp.asarray(np.asarray(a))
    g, u = jnp.dot(host(x), host(wg)), jnp.dot(host(x), host(wu))
    mid, gate_back = jax.vjp(lambda g, u: nn.silu(g) * u, g, u)
    dg, du = gate_back(jnp.dot(host(dy_h), host(row).T))
    _assert_rounded_once(_one_gemm(mid, dy_h), dwd, "down", False)
    _assert_rounded_once(_one_gemm(x, dg), dwg, "gate", False)
    _assert_rounded_once(_one_gemm(x, du), dwu, "up", False)


def test_the_compiled_ring_holds_permutes_and_no_model_all_reduce():
    """On data=2 x model=2 the sublayer's compiled program moves
    activations by ``collective-permute`` under ``tp/ring`` alone: no
    all-reduce, all-gather or reduce-scatter of a ``[B, T, .]`` array."""
    from deepspeed_tpu.analysis.hlo_collectives import collectives

    mesh = _mesh(2, 2)
    x, cols, row = _inputs(mesh, jnp.float32, 2)
    ring = to.plan(x.shape[1], sites=2)
    text = jax.jit(jax.grad(
        _loss(lambda x, c, r: _ring_sublayer(ring, x, c, r)),
        argnums=(0, 1, 2))).lower(x, cols, row).compile().as_text()
    found = collectives(text)
    permutes = [c for c in found if c.kind == "collective-permute"]
    assert permutes and all(to.RING_SCOPE in c.scope for c in permutes)
    assert [c for c in found
            if c.kind != "collective-permute" and c.max_rank >= 3] == []


# ------------------------------------------------------------------ #
# The rule
# ------------------------------------------------------------------ #
def test_plan_says_why_it_fell_back():
    groups.reset()
    assert to.plan(1024, sites=4) is None            # no topology
    _mesh(8, 1)
    with to.recording() as sites:
        assert to.plan(1024, sites=4) is None        # not tensor parallel
    assert sites == {"ring": 0, "steps": 0, "fallbacks": {}}
    mesh = _mesh(2, 4)
    with to.recording() as sites:
        assert to.plan(2048, sites=4) == to.Ring(mesh, 4)
        assert to.plan(2046, sites=4) is None
        assert to.plan(4 * (to.MIN_CHUNK_ROWS - 1), sites=4) is None
        assert to.plan(1024, sites=4, cache={}) is None
        assert to.plan(2048, sites=4, features=(6,)) is None
        inside = jax.shard_map(
            lambda x: x if to.plan(1024, sites=3) is None else x + 1,
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        assert float(jax.jit(inside)(jnp.zeros(()))) == 0.0
    assert sites["ring"] == 0
    assert sites["fallbacks"] == {
        "T % 4 != 0": 4, "a chunk under 512 rows": 4, "a KV cache": 4,
        "a width % 4 != 0": 4, "inside a manual region": 3}
    assert "16 fallbacks" not in to.describe(sites)
    assert to.describe(sites).startswith(
        "tp_overlap: 0 ring sites of 0 steps, 19 fallbacks (")
    groups.reset()
    groups.initialize_mesh(model_parallel_size=2, sequence_parallel_size=2,
                           data_parallel_size=2)
    with to.recording() as sites:
        assert to.plan(1024, sites=1) is None
    assert sites["fallbacks"] == {"a seq axis": 1}


def test_a_recorder_closes_itself_and_no_equal_one():
    """Two recorders open with equal counts (nested engines tracing
    before a site is noted): closing the inner leaves the outer open."""
    _mesh(2, 2)
    with to.recording() as outer:
        with to.recording() as inner:
            pass
        assert to.plan(1024, sites=4, cache={}) is None
    assert outer["fallbacks"] == {"a KV cache": 4}
    assert inner["fallbacks"] == {}


def _lowered_llama(seq, cache=False, batch=2):
    cfg = LlamaConfig.tiny(max_position_embeddings=2048)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((batch, seq), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)
    if cache:
        kv = jax.eval_shape(lambda: init_kv_cache(cfg, batch, seq))
        f = lambda p, i, c: model.apply(p, i, cache=c, cache_index=0)
        return jax.jit(f).lower(params, ids, kv).as_text()
    return jax.jit(lambda p, i: model.apply(p, i, labels=i)).lower(
        params, ids).as_text()


@pytest.mark.parametrize("case", ["one device", "T % n", "short chunk",
                                  "cache"])
def test_fallbacks_lower_to_the_text_without_the_ring(case, monkeypatch):
    """Where the rule does not engage the model is the ``nn.Dense`` one to
    the letter: its lowered text is the text lowered with the rule taken
    out altogether."""
    _mesh(8, 1) if case == "one device" else _mesh(4, 2)
    seq, why = {"one device": (1024, None), "T % n": (1023, "T % 2 != 0"),
                "short chunk": (1022, "a chunk under 512 rows"),
                "cache": (1024, "a KV cache")}[case]
    with to.recording() as sites:
        text = _lowered_llama(seq, cache=case == "cache")
    assert sites["ring"] == 0
    assert sites["fallbacks"] == ({why: 8} if why else {})
    assert "collective_permute" not in text and "shard_map" not in text
    monkeypatch.setattr(to, "plan", lambda *a, **k: None)
    assert _lowered_llama(seq, cache=case == "cache") == text


def test_the_ring_engages_in_the_model_and_not_while_it_initialises():
    _mesh(4, 2)
    cfg = LlamaConfig.tiny(max_position_embeddings=2048)
    model = LlamaForCausalLM(cfg)
    ids = jnp.zeros((2, 1024), jnp.int32)
    with to.recording() as sites:
        params = jax.eval_shape(model.init, jax.random.key(0), ids)
    assert sites == {"ring": 0, "steps": 0, "fallbacks": {}}
    with to.recording() as sites:
        text = jax.jit(lambda p, i: model.apply(p, i, labels=i)).lower(
            params, ids).as_text()
    assert sites == {"ring": 4 * cfg.num_hidden_layers, "steps": 2,
                     "fallbacks": {}}
    assert "collective_permute" in text
    # the parameter tree is nn.Dense's
    layer = params["params"]["model"]["layers_0"]
    assert sorted(layer["self_attn"]) == ["k_proj", "o_proj", "q_proj",
                                          "v_proj"]
    assert sorted(layer["mlp"]) == ["down_proj", "gate_proj", "up_proj"]
    assert layer["mlp"]["down_proj"]["kernel"].shape == (128, 64)


def test_engine_counts_the_mistral_cells_sites():
    """Six layers at 2 x 4,096 tokens on data=2 x model=2, the four-chip
    cell's shapes at tiny widths: 24 ring sites of 2 steps."""
    import deepspeed_tpu
    from deepspeed_tpu.models.mistral import (MistralConfig,
                                              MistralForCausalLM)

    groups.reset()
    topo = groups.initialize_mesh(model_parallel_size=2,
                                  data_parallel_size=2,
                                  devices=jax.devices()[:4])
    cfg = MistralConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=8192, sliding_window=4096,
        dtype=jnp.bfloat16)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=MistralForCausalLM(cfg), topology=topo, config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3}, "bf16": {"enabled": True},
            "gradient_clipping": 1.0})
    assert engine.tp_overlap_sites is None
    ids = np.zeros((2, 4096), np.int32)
    loss = engine(ids, ids)
    engine.backward(loss)
    engine.step()
    assert np.isfinite(float(loss))
    assert engine.tp_overlap_sites == {"ring": 24, "steps": 2,
                                       "fallbacks": {}}
    assert to.describe(engine.tp_overlap_sites) == \
        "tp_overlap: 24 ring sites of 2 steps, 0 fallbacks"
