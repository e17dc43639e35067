"""ZeRO sharding-policy tests (reference: tests/unit/runtime/zero/)."""

import functools
import re

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.analysis.hlo_collectives import collectives, summary
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import MeshTopology, ParallelDims
from deepspeed_tpu.runtime.zero import ZeroShardings, shard_leaf_spec


def _topo(**kw):
    return MeshTopology(ParallelDims(**kw))


def test_shard_leaf_picks_divisible_dim():
    topo = _topo(data=8)
    spec = shard_leaf_spec((16, 3), None, topo)
    assert spec == P(("dout", "data", "seq", "expert"), None)


def test_shard_leaf_respects_base_tp():
    topo = _topo(data=4, model=2)
    # dim0 sharded by TP already; ZeRO goes to dim1
    spec = shard_leaf_spec((8, 8), P("model", None), topo)
    assert spec == P("model", ("dout", "data", "seq", "expert"))


def test_shard_leaf_combines_on_same_dim():
    topo = _topo(data=4, model=2)
    # dim1 too small; dim0 already sharded by model but 16/2=8 divisible by 4
    spec = shard_leaf_spec((16, 3), P("model", None), topo)
    assert spec == P(("model", "dout", "data", "seq", "expert"), None)


def test_small_param_stays_replicated():
    topo = _topo(data=8)
    spec = shard_leaf_spec((16,), None, topo, min_size=100)
    assert spec == P()


def test_indivisible_stays_replicated():
    topo = _topo(data=8)
    spec = shard_leaf_spec((3, 5), None, topo)
    assert spec == P(None, None)


def test_stage_policies():
    topo = _topo(data=8)
    shapes = {"w": jax.ShapeDtypeStruct((16, 16), np.float32)}

    for stage, (p_sharded, m_sharded, g_sharded) in {
            0: (False, False, False),
            1: (False, True, False),
            2: (False, True, True),
            3: (True, True, True)}.items():
        zs = ZeroShardings(stage, topo)
        p = zs.param_specs(shapes)["w"]
        m = zs.master_specs(shapes)["w"]
        g = zs.grad_specs(shapes)["w"]
        assert (p != P()) == p_sharded, f"stage {stage} params"
        assert (m != P()) == m_sharded, f"stage {stage} master"
        assert (g != P()) == g_sharded, f"stage {stage} grads"


def test_stage3_persistence_threshold():
    topo = _topo(data=8)
    shapes = {"big": jax.ShapeDtypeStruct((1024, 8), np.float32),
              "small": jax.ShapeDtypeStruct((8, 8), np.float32)}
    zs = ZeroShardings(3, topo, param_persistence_threshold=1000)
    specs = zs.param_specs(shapes)
    assert specs["big"] != P()
    assert specs["small"] == P(None, None) or specs["small"] == P()
    # master always shards regardless of persistence floor
    m = zs.master_specs(shapes)
    assert m["small"] != P()


# What the TPU compiler writes and the CPU tests below never produce: a
# reduce-scatter as an ``all-reduce-scatter`` fusion, one asynchronous
# collective cloned under one channel_id, a ``-start`` that lists operand and
# result.  Lines cut from the compiled Mistral-7B ZeRO-3 x TP step.
_TPU_HLO = """\
%all-reduce-scatter.3 (input.3: bf16[2,4096,7168]) -> bf16[2080,7168] {
  %all-reduce.240 = bf16[4160,7168]{1,0:T(8,128)(2,1)} all-reduce(%pad.57), channel_id=491, replica_groups={{0,2},{1,3}}, to_apply=%add.10.clone
}

%fused_computation.7 (param_0.1: bf16[2048,7168]) -> bf16[4096,7168] {
  %all-gather.309 = bf16[4096,7168]{1,0:T(8,128)(2,1)} all-gather(%param_0.1), channel_id=13, dimensions={0}, metadata={op_name="jit(fused)/zero/gather/sharding_constraint" stack_frame_id=17}
}

ENTRY %main.1 (p: bf16[2048,7168]) -> bf16[4096,7168] {
  %all-gather.311 = bf16[4096,7168]{1,0:T(8,128)(2,1)} all-gather(%param_0.2), channel_id=13, dimensions={0}, metadata={op_name="jit(fused)/zero/gather/sharding_constraint" stack_frame_id=17}
  %all-to-all.6 = f32[2,1,4096,2048]{3,2,1,0:T(8,128)} all-to-all(%broadcast.354), channel_id=136, dimensions={0}, metadata={op_name="jit(fused)/transpose(jvp(M))/model/norm/mul" stack_frame_id=158}
  %collective-permute-start = (s32[1,4096,1]{1,2,0:T(1,128)}, s32[1,4096,1]{1,2,0:T(1,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%fusion.736), channel_id=14, source_target_pairs={{0,0},{1,2}}
  %collective-permute-done = s32[1,4096,1]{1,2,0:T(1,128)} collective-permute-done(%collective-permute-start)
  %all-reduce.7 = (f32[], f32[]) all-reduce(%a, %b), channel_id=15, to_apply=%add
}
"""


def test_hlo_collectives_reads_the_tpu_compilers_forms():
    found = collectives(_TPU_HLO)
    assert [(c.kind, c.results) for c in found] == [
        ("reduce-scatter", (("bf16", (2080, 7168)),)),
        ("all-gather", (("bf16", (4096, 7168)),)),      # channel 13, once
        ("all-to-all", (("f32", (2, 1, 4096, 2048)),)),
        ("collective-permute", (("s32", (1, 4096, 1)),)),
        ("all-reduce", (("f32", ()), ("f32", ()))),
    ]
    assert found[1].scope == "jit(fused)/zero/gather/sharding_constraint"
    assert found[2].max_rank == 4
    by_kind = summary(found)
    assert by_kind["all-gather"] == {"count": 1, "bytes": 4096 * 7168 * 2}
    assert by_kind["all-reduce"] == {"count": 1, "bytes": 8}


# --------------------------------------------------------------------- #
# Stage 3 gathers its weights on use (engine._make_micro_grads): read off
# the compiled step, and checked against a single-device run.
# --------------------------------------------------------------------- #
_ADAMW = {"type": "AdamW", "params": {"lr": 3e-3, "weight_decay": 0.0}}


def _llama_engine(dp, tp, stage, gas=1, threshold=0, micro=1, dtype="bf16",
                  optimizer=_ADAMW, zero_keys=None):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    groups.reset()
    topo = groups.initialize_mesh(
        model_parallel_size=tp, data_parallel_size=dp,
        devices=jax.devices()[:dp * tp])
    cfg = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "optimizer": optimizer,
        "zero_optimization": {"stage": stage,
                              "stage3_param_persistence_threshold": threshold,
                              **(zero_keys or {})},
        "gradient_clipping": 1.0,
    }
    if dtype == "bf16":
        cfg["bf16"] = {"enabled": True}
    model_cfg = LlamaConfig.tiny(
        dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(model_cfg), config=cfg, topology=topo)
    return engine, model_cfg


def _token_batches(n, batch, vocab, seq=32, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
            for _ in range(n)]


def _step(engine, ids):
    loss = engine(ids, ids)
    engine.backward(loss)
    engine.step()
    return float(jax.device_get(loss))


def _lowered_step(dp, tp, stage, zero_keys=None, seq=32):
    engine, model_cfg = _llama_engine(dp, tp, stage, zero_keys=zero_keys)
    _step(engine, _token_batches(1, dp, model_cfg.vocab_size, seq=seq)[0])
    return engine, engine.lower_train_step()


def _compiled_step(dp, tp, stage, seq=32):
    engine, lowered = _lowered_step(dp, tp, stage, seq=seq)
    return engine, lowered.compile().as_text()


def _is_float(c):
    return all(dtype in ("bf16", "f16", "f32") for dtype, _ in c.results)


@pytest.mark.parametrize("dp,tp", [(2, 2), (4, 2), (4, 1)])
def test_stage3_gathers_weights_not_activations(dp, tp):
    """On a data x model mesh (and on a pure data mesh) the compiled fused
    step moves WEIGHTS over the ZeRO axes, under ``zero/gather``; no
    activation is resharded between 'data' and 'model'.  At the parent of
    PR 26 this program held 16 all-to-alls and gathers of [batch, seq, ...]
    activations: the stored specs alone read as 2-D tensor parallelism."""
    engine, hlo = _compiled_step(dp, tp, stage=3)
    found = collectives(hlo)
    # the embedding's backward scatter-add moves its [batch, seq, hidden]
    # cotangent (and the token ids) once, at every stage: not a resharding
    # the ungathered weight caused
    in_layers = [c for c in found if "embed_tokens" not in c.scope]
    assert [c for c in in_layers if c.kind == "all-to-all"] == []
    gathers = [c for c in in_layers if c.kind == "all-gather"]
    assert gathers, "stage 3 on a mesh gathers something"
    for c in gathers:
        assert _is_float(c) and c.max_rank <= 2, c   # parameter-shaped
        assert "zero/gather" in c.scope, c
    # every sharded leaf is gathered once: forward and backward share it
    assert len(gathers) <= len(jax.tree.leaves(engine.state["params"]))


def test_stage3_with_the_tp_ring_moves_activations_by_permute_alone(
        monkeypatch):
    """At a sequence long enough for ``parallel/tensor_overlap.py`` to
    engage (512 rows a rank) the compiled (2, 2) step holds no all-reduce
    of a ``[batch, seq, hidden]`` activation under a layer's scope: its
    layers move activations by ``collective-permute`` under ``tp/ring``.
    ZeRO's half is untouched: the weights are still gathered under
    ``zero/gather`` exactly as in the step without the ring, and the
    weight gradients summed over 'data' are the same arrays."""
    from deepspeed_tpu.parallel import tensor_overlap

    def weight_reductions(found, vocab=256):
        """Sizes of the layers' weight-shaped arrays the step sums over
        'data'.  A set: the CPU compiler combines several arrays into one
        all-reduce (the step compiled for a v5e holds the parent's
        weight-gradient collectives one for one:
        ``tools/chip_calls/pr62_tp_ring.py --aot``)."""
        return {int(np.prod(shape))
                for c in found for _dtype, shape in c.results
                if c.kind in ("all-reduce", "reduce-scatter")
                and len(shape) == 2 and vocab not in shape}

    def weight_gathers(found):
        return sorted(c.results for c in found if c.kind == "all-gather"
                      and "zero/gather" in c.scope)

    engine, hlo = _compiled_step(2, 2, stage=3, seq=1024)
    assert engine.tp_overlap_sites == {"ring": 8, "steps": 2,
                                       "fallbacks": {}}
    found = collectives(hlo)
    in_layers = [c for c in found if "/layers_" in c.scope]
    assert [c for c in in_layers
            if c.kind in ("all-reduce", "all-to-all", "reduce-scatter")
            and c.max_rank >= 3] == []
    # read off the lines: on the CPU every ``ppermute`` of the step shares
    # one channel id, and ``collectives`` lists a channel once
    permutes = [line for line in hlo.splitlines()
                if re.search(r"= f32\[[0-9,]*\]\S* collective-permute"
                             r"(-start)?\(", line) and "/layers_" in line]
    assert len(permutes) >= 8
    assert all(tensor_overlap.RING_SCOPE + "/ppermute" in line
               for line in permutes)
    gathers = [c for c in in_layers if c.kind == "all-gather"]
    assert all(c.max_rank <= 2 and "zero/gather" in c.scope
               for c in gathers if _is_float(c))
    monkeypatch.setattr(tensor_overlap, "plan", lambda *a, **k: None)
    plain_engine, plain = _compiled_step(2, 2, stage=3, seq=1024)
    assert plain_engine.tp_overlap_sites is None
    assert [c for c in collectives(plain) if "/layers_" in c.scope
            and c.kind == "all-reduce" and c.max_rank >= 3]
    assert weight_gathers(found) == weight_gathers(collectives(plain))
    assert weight_reductions(found) == weight_reductions(collectives(plain))


@pytest.mark.parametrize("dp,tp,stage", [(2, 2, 1), (1, 1, 3), (1, 1, 1)])
def test_gather_on_use_adds_nothing_where_nothing_is_sharded(dp, tp, stage):
    """Below stage 3, and on one device, the step holds no instruction of
    the gather: its scope is empty in the compiled program."""
    _, hlo = _compiled_step(dp, tp, stage)
    assert "zero/gather" not in hlo
    if dp * tp == 1:
        assert collectives(hlo) == []


@pytest.mark.parametrize("dp,tp,gas,threshold,seq", [
    (2, 2, 1, 0, 32), (4, 2, 1, 0, 32), (4, 1, 1, 0, 32),
    (2, 2, 2, 0, 32),    # gas 2: the micro + apply programs, not the fused
    (2, 2, 1, 100, 32),  # the norms (64 elements) stay unsharded
    (2, 2, 1, 0, 1024),  # 512 rows a rank: TP's collectives as ring steps
])
def test_stage3_on_mesh_matches_single_device(dp, tp, gas, threshold, seq):
    """Three optimizer steps from one seed: losses and master weights of
    stage 3 on the mesh agree with stage 0 on one device, to the tolerance
    of test_engine.py::test_zero_stages_agree.  SGD, because its update is
    linear in the gradient: the partitioning sums in another order, and
    Adam's g / sqrt(v) turns that into 3e-5 on the few elements whose
    gradient is near zero."""
    batch, steps = 4, 3
    sgd = {"type": "SGD", "params": {"lr": 0.1, "momentum": 0.9}}
    runs = []
    for (d, t, stage) in ((1, 1, 0), (dp, tp, 3)):
        engine, model_cfg = _llama_engine(
            d, t, stage, gas=gas, threshold=threshold if stage else 0,
            micro=batch // d, dtype="fp32", optimizer=sgd)
        losses = [_step(engine, ids) for ids in
                  _token_batches(steps * gas, batch, model_cfg.vocab_size,
                                 seq=seq)]
        assert engine.global_steps == steps
        ring_sites = (engine.tp_overlap_sites or {}).get("ring", 0)
        assert ring_sites == (8 if stage == 3 and seq >= 1024 else 0)
        runs.append((losses, jax.device_get(engine.state["master"])))
    if threshold:
        specs = jax.tree.leaves(
            jax.tree.map(lambda x: x.sharding.spec, engine.state["params"]))
        assert any(s == P() or s == P(None) for s in specs)
    (ref_losses, ref_master), (losses, master) = runs
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(ref_master), jax.tree.leaves(master)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# One gradient path: XLA's scheduler places the collectives; the keys
# DeepSpeed's bucket machinery reads are accepted and change nothing.
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("stage", [1, 2, 3],
                         ids=["stage1", "stage2", "stage3"])
def test_zero_step_has_no_optimization_barrier(stage):
    """The fused step on the 8-device mesh chains nothing: neither the
    lowered nor the compiled program holds an optimization barrier, even
    with buckets of a few leaves asked for (the bucket chain the chip
    measured as a loss put one barrier per bucket there)."""
    _, lowered = _lowered_step(4, 2, stage, zero_keys={
        "overlap_comm": True, "reduce_bucket_size": 4096,
        "allgather_bucket_size": 4096})
    assert "optimization_barrier" not in lowered.as_text()
    assert "opt-barrier" not in lowered.compile().as_text()


@functools.lru_cache(maxsize=None)
def _default_stage3_text():
    return _lowered_step(2, 2, 3)[1].as_text()


@pytest.mark.parametrize("key,value", [
    ("overlap_comm", True), ("overlap_comm", False),
    ("reduce_bucket_size", 4096), ("allgather_bucket_size", 4096),
    ("stage3_prefetch_bucket_size", 1024),
    ("stage3_max_live_parameters", 1024),
    ("stage3_max_reuse_distance", 1024),
    ("contiguous_gradients", False), ("reduce_scatter", False),
    ("allgather_partitions", False), ("round_robin_gradients", True),
    ("ignore_unused_parameters", False), ("sub_group_size", 1024),
    ("stage3_model_persistence_threshold", 1024),
    ("memory_efficient_linear", False),
], ids=lambda v: v if isinstance(v, str) else str(v).lower())
def test_zero_parity_keys_are_inert(key, value):
    """Every key ``ZeroConfig``'s doc calls accepted for parity loads at a
    non-default value and leaves the lowered stage-3 step byte-identical."""
    engine, lowered = _lowered_step(2, 2, 3, zero_keys={key: value})
    field = key.removeprefix("stage3_")
    assert getattr(engine.config.zero_config, field) == value
    assert lowered.as_text() == _default_stage3_text()
