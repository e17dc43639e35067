"""ZeRO-Offload tests (reference: tests/unit/runtime/zero/test_zero_offloadpp.py
and the offload paths of test_zero.py).

Offloaded optimizer state must live in host memory between steps, training
must match the non-offloaded engine bit-for-bit (same jitted update, same
order of operations), and the twin-flow ratio must control the offloaded
fraction.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))

import deepspeed_tpu
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.zero.offload import (HOST_MEMORY_KIND, OffloadPlan,
                                                validate_offload_config)
from simple_model import SimpleModel, random_batch, train_steps

HIDDEN = 16


def _config(zero_stage=2, offload=None, **extra):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": zero_stage},
        "gradient_clipping": 1.0,
    }
    if offload is not None:
        cfg["zero_optimization"]["offload_optimizer"] = offload
    cfg.update(extra)
    return cfg


def _engine(cfg):
    model = SimpleModel(hidden_dim=HIDDEN)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=(model.init, model.apply), config=cfg)
    return engine


def _memory_kinds(tree):
    return {l.sharding.memory_kind for l in jax.tree.leaves(tree)}


def test_offload_state_lives_on_host():
    engine = _engine(_config(offload={"device": "cpu"}))
    train_steps(engine, steps=2, batch=16, hidden_dim=HIDDEN)
    assert _memory_kinds(engine.state["master"]) == {HOST_MEMORY_KIND}
    assert _memory_kinds(engine.state["opt"]) == {HOST_MEMORY_KIND}
    # compute params stay on device
    assert HOST_MEMORY_KIND not in _memory_kinds(engine.state["params"])


@pytest.mark.parametrize("zero_stage", [1, 2, 3])
def test_offload_matches_no_offload(zero_stage):
    """Same jitted update either way -> losses match exactly-ish."""
    ref = _engine(_config(zero_stage))
    off = _engine(_config(zero_stage, offload={"device": "cpu"}))
    l_ref = train_steps(ref, steps=6, batch=16, hidden_dim=HIDDEN)
    l_off = train_steps(off, steps=6, batch=16, hidden_dim=HIDDEN)
    np.testing.assert_allclose(l_off, l_ref, rtol=1e-6)
    m_ref = jax.device_get(ref.state["master"])
    m_off = jax.device_get(off.state["master"])
    for a, b in zip(jax.tree.leaves(m_ref), jax.tree.leaves(m_off)):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_twin_flow_ratio_partial_offload():
    """ratio=0.5 offloads only the largest leaves (~half the elements)."""
    engine = _engine(_config(offload={"device": "cpu", "ratio": 0.5}))
    train_steps(engine, steps=2, batch=16, hidden_dim=HIDDEN)
    plan = engine._offload_plan
    assert 0.4 <= plan.fraction < 1.0
    kinds = _memory_kinds(engine.state["master"])
    assert HOST_MEMORY_KIND in kinds and len(kinds) == 2  # mixed placement
    # the offloaded set is the largest-first prefix: every offloaded leaf is
    # at least as large as every device-resident leaf
    masks = jax.tree.leaves(plan.mask)
    sizes = [int(np.prod(l.shape))
             for l in jax.tree.leaves(engine.state["master"])]
    off_sizes = [s for s, m in zip(sizes, masks) if m]
    on_sizes = [s for s, m in zip(sizes, masks) if not m]
    assert not on_sizes or min(off_sizes) >= max(on_sizes)


def test_offload_plan_ratio_bounds():
    shapes = jax.eval_shape(lambda: {"a": jnp.zeros((100,)),
                                     "b": jnp.zeros((10,))})
    assert OffloadPlan(shapes, 1.0).fraction == 1.0
    assert OffloadPlan(shapes, 0.0).fraction == 0.0
    p = OffloadPlan(shapes, 0.5)
    assert p.mask["a"] is True and p.mask["b"] is False
    with pytest.raises(ValueError):
        OffloadPlan(shapes, 1.5)


def test_nvme_offload_requires_path():
    # nvme offload is implemented (see test_native_ops.py); without a
    # swap directory it must still fail loudly
    with pytest.raises(ValueError, match="nvme_path"):
        _engine(_config(offload={"device": "nvme"}))


def test_offload_requires_zero():
    with pytest.raises(ValueError, match="stage"):
        _engine(_config(zero_stage=0, offload={"device": "cpu"}))


def test_offload_checkpoint_roundtrip(tmp_path):
    engine = _engine(_config(offload={"device": "cpu"}))
    train_steps(engine, steps=3, batch=16, hidden_dim=HIDDEN)
    engine.save_checkpoint(str(tmp_path), tag="t")
    fresh = _engine(_config(offload={"device": "cpu"}))
    x, y = random_batch(16, HIDDEN)
    fresh.forward(x[:, :], y)  # materialise state
    fresh.load_checkpoint(str(tmp_path), tag="t")
    a = jax.device_get(engine.state["master"])
    b = jax.device_get(fresh.state["master"])
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(la, lb)


# ------------------------------------------------------------------ #
# offload_param (ZeRO-Infinity param tier at host granularity —
# reference zero/partition_parameters.py NVMe/host path)
# ------------------------------------------------------------------ #
def test_offload_param_host_residency_and_parity():
    import jax

    groups.initialize_mesh()
    base_cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 3,
                              "param_persistence_threshold": 0},
    }
    ref = _engine(base_cfg)
    ref_losses = train_steps(ref, steps=5, batch=16, hidden_dim=HIDDEN)

    groups.reset()
    groups.initialize_mesh()
    cfg = {**base_cfg,
           "zero_optimization": {**base_cfg["zero_optimization"],
                                 "offload_param": {"device": "cpu"}}}
    e = _engine(cfg)
    losses = train_steps(e, steps=5, batch=16, hidden_dim=HIDDEN)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    # params are HOST-resident between steps
    assert e._params_on_host
    leaf = jax.tree.leaves(e.state["params"])[0]
    assert leaf.sharding.memory_kind == "pinned_host", \
        leaf.sharding.memory_kind


def test_offload_param_requires_stage3():
    groups.initialize_mesh()
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2,
                              "offload_param": {"device": "cpu"}},
    }
    with pytest.raises(ValueError, match="stage 3"):
        _engine(cfg)


# ------------------------------------------------------------------ #
# ZeRO-Infinity param tier: offload_param.device='nvme' (reference
# runtime/swap_tensor/partitioned_param_swapper.py:36)
# ------------------------------------------------------------------ #
def _param_cfg(device, path=None):
    cfg = _config(zero_stage=3)
    blk = {"device": device}
    if path is not None:
        blk["nvme_path"] = str(path)
    cfg["zero_optimization"]["offload_param"] = blk
    return cfg


def test_nvme_param_offload_matches_no_offload(tmp_path):
    """Params living in NVMe swap files between steps (pipelined AIO
    restore each forward) must train identically to no offload."""
    ref = _engine(_config(zero_stage=3))
    off = _engine(_param_cfg("nvme", tmp_path))
    l_ref = train_steps(ref, steps=4, batch=16, hidden_dim=HIDDEN)
    l_off = train_steps(off, steps=4, batch=16, hidden_dim=HIDDEN)
    np.testing.assert_allclose(l_off, l_ref, rtol=1e-6)
    # swap files exist on "NVMe"
    import os
    swp = [f for _r, _d, fs in os.walk(tmp_path) for f in fs
           if f.endswith(".swp")]
    assert swp, "no swap files written under nvme_path"


def test_nvme_param_offload_host_leaves_are_memmaps(tmp_path):
    """Between steps the swapped params are read-only memmaps (evictable
    page cache), not RAM arrays."""
    eng = _engine(_param_cfg("nvme", tmp_path))
    train_steps(eng, steps=2, batch=16, hidden_dim=HIDDEN)
    # epilogue leaves params on the nvme tier
    leaves = jax.tree.leaves(eng.state["params"])
    assert all(isinstance(l, np.memmap) for l in leaves), \
        [type(l) for l in leaves]


def test_nvme_param_offload_requires_path():
    with pytest.raises(ValueError, match="nvme_path"):
        _engine(_param_cfg("nvme"))


def test_nvme_swapper_rss_bounded(tmp_path):
    """Swapping out a tree must not leave its bytes RAM-resident, and the
    pipelined device restore must hold at most ~two leaves in flight —
    host RSS stays well below total tree bytes (the point of the
    ZeRO-Infinity param tier)."""
    import gc
    import os

    from deepspeed_tpu.runtime.swap_tensor import PartitionedOptimizerSwapper

    def rss_bytes():
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")

    sw = PartitionedOptimizerSwapper(str(tmp_path))
    # leaves > glibc's max dynamic mmap threshold (32MB) so freed numpy
    # buffers are returned to the OS and RSS actually reflects residency
    n_leaves, leaf_bytes = 4, 40 * 1024 * 1024
    total = n_leaves * leaf_bytes

    def make(i):
        # float32: jax (x64 disabled) would silently downcast float64
        # leaves at device_put, breaking exact comparison
        return np.random.default_rng(i).standard_normal(
            (leaf_bytes // 4,)).astype(np.float32)

    gc.collect()
    base = rss_bytes()
    tree = {f"p{i}": make(i) for i in range(n_leaves)}
    swapped = sw.swap_out_tree("params", tree)
    del tree
    gc.collect()
    after = rss_bytes() - base
    # the 160MB tree is gone from RAM (memmaps are not resident until
    # touched); allow generous slack for allocator noise
    assert after < total // 2, \
        f"RSS grew {after/1e6:.0f}MB for a {total/1e6:.0f}MB tree"
    # restore through the pipelined path and verify content parity
    import jax as _jax

    sh = jax.tree.map(
        lambda _l: _jax.sharding.SingleDeviceSharding(_jax.devices()[0]),
        swapped)
    back = sw.swap_in_tree_to_device("params", swapped, sh)
    for i in range(n_leaves):
        np.testing.assert_array_equal(np.asarray(back[f"p{i}"]), make(i))


# ------------------------------------------------------------------ #
# Pipelined host-Adam (per-bucket offload streams) — exercised through
# the single-device MiniOffloadEngine twin, which runs the ENGINE'S OWN
# unbound step methods (see runtime/zero/offload_twin.py), so these
# results hold for the engine code itself on hosts where the full
# multi-axis engine cannot construct.
# ------------------------------------------------------------------ #
from deepspeed_tpu.runtime.zero.offload import (  # noqa: E402
    OffloadTransferStats, partition_transfer_buckets)
from deepspeed_tpu.runtime.zero.offload_twin import MiniOffloadEngine


def _twin_run(pipeline, fp16=False, steps=4, buffer_count=3,
              overflow_at=None, seed=0):
    eng = MiniOffloadEngine(pipeline=pipeline, fp16=fp16,
                            buffer_count=buffer_count, seed=seed)
    gnorms = []
    for t in range(steps):
        g = eng.synthetic_grads(t)
        if overflow_at is not None and t == overflow_at:
            g[0] = g[0] * np.float32(np.inf)
        eng.set_acc_grads(g)
        gnorms.append(float(jax.device_get(eng.step())))
    eng.sync()
    return eng, gnorms


def _assert_twin_states_equal(a, b):
    for name in ("master", "params", "acc_grads"):
        for la, lb in zip(jax.tree.leaves(a.state[name]),
                          jax.tree.leaves(b.state[name])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in a.state["opt"]:
        for la, lb in zip(jax.tree.leaves(a.state["opt"][k]),
                          jax.tree.leaves(b.state["opt"][k])):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for s in ("step", "opt_step", "loss_scale", "good_steps",
              "hysteresis"):
        assert float(jax.device_get(a.state[s])) == \
            float(jax.device_get(b.state[s])), s


def test_pipelined_twin_bit_exact_fp32():
    """>=3 steps through the per-bucket pipelined arm produce BIT-equal
    master/opt/params/scalars vs the synchronous whole-tree boundary."""
    sync, gn_s = _twin_run(pipeline=False, steps=4)
    pipe, gn_p = _twin_run(pipeline=True, steps=4)
    assert gn_s == gn_p
    _assert_twin_states_equal(sync, pipe)
    assert int(jax.device_get(pipe.state["opt_step"])) == 4


def test_pipelined_twin_bit_exact_fp16_overflow_skip():
    """fp16 with an inf gradient on step 1: both arms must SKIP that
    update (opt_step stays behind step), halve the loss scale through
    the shared _loss_scale_next bookkeeping, and stay bit-exact."""
    sync, gn_s = _twin_run(pipeline=False, fp16=True, steps=4,
                           overflow_at=1)
    pipe, gn_p = _twin_run(pipeline=True, fp16=True, steps=4,
                           overflow_at=1)
    assert gn_s == gn_p
    _assert_twin_states_equal(sync, pipe)
    assert int(jax.device_get(pipe.state["step"])) == 4
    assert int(jax.device_get(pipe.state["opt_step"])) == 3  # one skip
    # hysteresis=2: a single overflow drains the counter but does NOT
    # lower the scale yet (reference DynamicLossScaler semantics)
    assert int(jax.device_get(pipe.state["hysteresis"])) == 1
    assert float(jax.device_get(pipe.state["loss_scale"])) == 2.0 ** 8


def test_pipelined_twin_mid_pipeline_fetch_drains():
    """Fetching the whole state tree right after a pipelined step — the
    checkpoint path's read — must drain every in-flight bucket stream:
    the snapshot equals the synchronous arm's, and training continues
    bit-exact afterwards."""
    sync, _ = _twin_run(pipeline=False, steps=2)
    pipe = MiniOffloadEngine(pipeline=True, buffer_count=3, seed=0)
    for t in range(2):
        pipe.set_acc_grads(pipe.synthetic_grads(t))
        pipe.step()
    # NO sync() first: device_get itself must wait out the streams
    snap = jax.device_get(pipe.state)
    ref = jax.device_get(sync.state)
    for la, lb in zip(jax.tree.leaves(ref), jax.tree.leaves(snap)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # the mid-pipeline read must not corrupt subsequent steps
    for t in range(2, 4):
        for e in (sync, pipe):
            e.set_acc_grads(e.synthetic_grads(t))
            e.step()
    sync.sync(), pipe.sync()
    _assert_twin_states_equal(sync, pipe)


def test_pipelined_twin_traceguard_steady_state():
    """Warmed pipelined steps: 0 backend compiles and 0 host syncs —
    the per-bucket programs compile once and the hot loop never blocks
    (profiling waits live behind the opt-in timed_wait helper)."""
    from deepspeed_tpu.analysis.trace_guard import TraceGuard

    eng = MiniOffloadEngine(pipeline=True, buffer_count=3, seed=0)
    grads = [eng.synthetic_grads(t) for t in range(5)]
    for t in range(3):                      # warm: compiles land here
        eng.set_acc_grads(grads[t])
        eng.step()
    eng.sync()
    with TraceGuard(max_compiles=0, max_host_syncs=0,
                    label="pipelined offload steady state") as tg:
        for t in range(3, 5):
            eng.set_acc_grads(grads[t])
            eng.step()
    eng.sync()
    assert tg.compiles == 0 and tg.host_syncs == 0


def test_pipelined_twin_transfer_stats():
    """The hot path feeds the observability gauges: every step spills
    and restores the full offloaded byte volume, and with >1 bucket the
    structural overlap fraction is strictly positive."""
    eng, _ = _twin_run(pipeline=True, steps=3, buffer_count=3)
    stats = eng._offload_stats
    snap = stats.snapshot()
    assert snap["observability/offload_pipeline_steps"] == 3
    assert snap["observability/offload_restored_bytes"] == \
        snap["observability/offload_spilled_bytes"] > 0
    assert 0.0 < snap["observability/offload_overlap_fraction"] <= 1.0


# ------------------------------------------------------------------ #
# Unit coverage for the pipelining building blocks
# ------------------------------------------------------------------ #
def test_partition_transfer_buckets_balance_and_determinism():
    sizes = [100, 1, 1, 50, 50, 2, 97, 3]
    a = partition_transfer_buckets(sizes, 3)
    b = partition_transfer_buckets(list(sizes), 3)
    assert a == b                                   # deterministic
    assert sorted(i for bk in a for i in bk) == list(range(len(sizes)))
    loads = [sum(sizes[i] for i in bk) for bk in a]
    # LPT bound: max load <= 4/3 * optimal (optimal >= total/n)
    assert max(loads) <= (4 / 3) * (sum(sizes) / 3) + max(sizes) / 3
    assert [bk[0] for bk in a] == sorted(bk[0] for bk in a)


def test_partition_transfer_buckets_edges():
    with pytest.raises(ValueError, match="num_buckets"):
        partition_transfer_buckets([1, 2], 0)
    assert partition_transfer_buckets([], 4) == []
    # fewer leaves than buckets -> fewer (non-empty) buckets
    assert partition_transfer_buckets([5, 7], 4) == [[0], [1]]
    assert partition_transfer_buckets([5, 7, 9], 1) == [[0, 1, 2]]


def test_offload_plan_pipeline_buckets_partial_ratio():
    """Buckets cover exactly the offloaded leaves; twin-flow residents
    come back separately for the in-place update path."""
    shapes = jax.eval_shape(lambda: {
        "big_a": jnp.zeros((1000,)), "big_b": jnp.zeros((900,)),
        "mid": jnp.zeros((100,)), "tiny": jnp.zeros((4,))})
    plan = OffloadPlan(shapes, ratio=0.9)
    buckets, resident = plan.pipeline_buckets(2)
    offloaded = sorted(i for b in buckets for i in b)
    assert sorted(offloaded + resident) == list(range(4))
    flat_mask = plan.flat_mask
    assert all(flat_mask[i] for i in offloaded)
    assert not any(flat_mask[i] for i in resident)
    assert len(buckets) == 2 and all(buckets)


def test_offload_pipeline_config_property():
    from deepspeed_tpu.runtime.config import OffloadOptimizerConfig

    assert not OffloadOptimizerConfig(device="cpu").pipeline_enabled
    assert OffloadOptimizerConfig(device="cpu",
                                  pipeline=True).pipeline_enabled
    assert OffloadOptimizerConfig(device="cpu",
                                  pipeline_read=True).pipeline_enabled
    assert OffloadOptimizerConfig(device="cpu",
                                  pipeline_write=True).pipeline_enabled


def test_transfer_stats_structural_overlap():
    st = OffloadTransferStats()
    st.note_restore(100, overlapped=False)      # first bucket exposed
    st.note_restore(100, overlapped=True)
    st.note_spill(100, overlapped=True)
    st.note_spill(100, overlapped=True)
    st.note_step(buckets=2)
    snap = st.snapshot()
    assert snap["observability/offload_transfers"] == 4
    assert snap["observability/offload_overlap_fraction"] == 0.75
    assert snap["observability/offload_pipeline_steps"] == 1
    assert snap["observability/offload_buckets"] == 2


def test_engine_pipelined_offload_parity():
    """Full-engine pipelined-vs-sync parity (needs the multi-axis mesh
    engine; skipped on hosts where it cannot construct — the twin tests
    above cover the same code paths single-device)."""
    try:
        ref = _engine(_config(offload={"device": "cpu"}))
    except Exception as e:  # noqa: BLE001 — jax-version-gated engine
        pytest.skip(f"full engine unavailable on this host: {e}")
    pipe = _engine(_config(offload={"device": "cpu", "pipeline": True,
                                    "buffer_count": 3}))
    l_ref = train_steps(ref, steps=4, batch=16, hidden_dim=HIDDEN)
    l_pipe = train_steps(pipe, steps=4, batch=16, hidden_dim=HIDDEN)
    np.testing.assert_allclose(l_pipe, l_ref, rtol=0, atol=0)
    for a, b in zip(jax.tree.leaves(jax.device_get(ref.state["master"])),
                    jax.tree.leaves(jax.device_get(pipe.state["master"]))):
        np.testing.assert_array_equal(a, b)
