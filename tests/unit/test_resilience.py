"""Fault-tolerant checkpointing tests: the atomic commit protocol, the
corruption-detection matrix (truncation, bit flips, missing manifest /
shard, stale ``latest``), save-crash injection at every chaos fault
point, retention GC, the NaN/loss-spike sentinel, and the kill-mid-save
auto-resume smoke tool.

Everything runs single-device CPU: the corruption matrix drives the REAL
``save_engine_state`` / ``load_engine_state`` paths through the smoke
tool's ``MiniEngine``.
"""

import csv
import importlib.util
import json
import os
import pathlib
import shutil
import types

import numpy as np
import pytest

from deepspeed_tpu.checkpoint import AsyncCheckpointEngine
from deepspeed_tpu.resilience import (ResilienceMetrics, ResilientTrainLoop,
                                      apply_retention, chaos, manifest)

_TOOL = pathlib.Path(__file__).resolve().parents[2] / "tools" / \
    "chaos_smoke.py"
_spec = importlib.util.spec_from_file_location("chaos_smoke", _TOOL)
CS = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(CS)


@pytest.fixture(autouse=True)
def _disarm_chaos():
    chaos.disarm()
    yield
    chaos.disarm()


def _flat(tree):
    return CS._flat(tree)


def _make_ckpts(tmp_path, steps=(2, 4)):
    """Train a MiniEngine, checkpointing at each step in ``steps``.
    Returns (engine, {step: master_flat_at_that_step})."""
    eng = CS.MiniEngine(seed=0)
    want = {}
    step = 0
    for target in steps:
        while step < target:
            eng.train_micro_batch(*CS.batch_fn(step))
            step += 1
        eng.save_checkpoint(str(tmp_path), tag=f"t{target}")
        want[target] = _flat(eng.state["master"])
    return eng, want


def _shard_file(tag_dir):
    files = [f for f in os.listdir(tag_dir) if f.endswith("_states.npz")]
    assert len(files) == 1, files
    return os.path.join(tag_dir, files[0])


def _load_fresh(tmp_path, tag=None, **kw):
    eng = CS.MiniEngine(seed=1)  # different init: loading must overwrite
    path, cs = eng.load_checkpoint(str(tmp_path), tag=tag, **kw)
    return eng, path, cs


# --------------------------------------------------------------------- #
# Atomic commit protocol
# --------------------------------------------------------------------- #
def test_atomic_save_layout_and_manifest(tmp_path):
    _make_ckpts(tmp_path, steps=(2, 4))
    assert manifest.read_latest(str(tmp_path)) == "t4"
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]
    for tag in ("t2", "t4"):
        tag_dir = tmp_path / tag
        ok, problems = manifest.verify_tag(str(tag_dir))
        assert ok, problems
        mf = json.load(open(tag_dir / "manifest.json"))
        assert mf["tag"] == tag
        assert mf["topology"]["process_count"] == 1
        assert mf["framework_version"]
        shards = mf["shards"]
        assert "client_state.json" in shards
        assert any(k.endswith("_states.npz") for k in shards)
        for entry in shards.values():
            assert entry["bytes"] > 0 and isinstance(entry["crc32"], int)
        # no checksum sidecars survive the merge
        assert not [f for f in os.listdir(tag_dir) if f.endswith(".crc.json")]


# only the checkpoint-path fault points live on the save path; the
# supervision points (worker_crash / worker_hang / heartbeat_stall) fire
# in the train loop and heartbeat and are covered by test_supervisor.py
@pytest.mark.parametrize("point", ["slow_io", "crash_after_shard_write",
                                   "corrupt_shard_bytes",
                                   "fail_latest_publish"])
def test_save_crash_at_every_fault_point_keeps_latest_verified(
        tmp_path, point):
    """The crash-recovery invariant: a save dying at ANY fault point
    leaves ``latest`` pointing at a fully verified tag, and a fresh
    engine restores it bit-exact."""
    eng, want = _make_ckpts(tmp_path, steps=(2,))
    chaos.arm(point, action="raise")
    with pytest.raises(chaos.ChaosInjectedError):
        eng.save_checkpoint(str(tmp_path), tag="torn")
    chaos.disarm(point)

    assert manifest.read_latest(str(tmp_path)) == "t2"
    ok, problems = manifest.verify_tag(str(tmp_path / "t2"))
    assert ok, problems
    if point == "fail_latest_publish":
        # staged dir was renamed (complete + verified) but never published
        assert (tmp_path / "torn").is_dir()
        assert manifest.verify_tag(str(tmp_path / "torn"))[0]
    else:
        assert not (tmp_path / "torn").is_dir()
        assert (tmp_path / "torn.tmp").is_dir()

    fresh, path, _ = _load_fresh(tmp_path)
    assert path is not None and path.endswith("t2")
    got = _flat(fresh.state["master"])
    for k in want[2]:
        assert np.array_equal(got[k], want[2][k]), k


def test_resave_same_tag_after_crash_cleans_staging(tmp_path):
    eng, _ = _make_ckpts(tmp_path, steps=(2,))
    with chaos.inject("crash_after_shard_write", action="raise"):
        with pytest.raises(chaos.ChaosInjectedError):
            eng.save_checkpoint(str(tmp_path), tag="t9")
    assert (tmp_path / "t9.tmp").is_dir()
    eng.save_checkpoint(str(tmp_path), tag="t9")  # retry succeeds
    assert not (tmp_path / "t9.tmp").is_dir()
    assert manifest.verify_tag(str(tmp_path / "t9"))[0]
    assert manifest.read_latest(str(tmp_path)) == "t9"


# --------------------------------------------------------------------- #
# Corruption matrix: every row must be detected at load and fall back
# to the newest verified tag (never silently corrupt, never a crash)
# --------------------------------------------------------------------- #
def _assert_falls_back_to_t2(tmp_path, want, metrics=None, **load_kw):
    fresh, path, _ = _load_fresh(tmp_path, metrics=metrics, **load_kw)
    assert path is not None and path.endswith("t2"), path
    got = _flat(fresh.state["master"])
    for k in want[2]:
        assert np.array_equal(got[k], want[2][k]), k


def test_bitflip_detected_and_falls_back(tmp_path):
    _, want = _make_ckpts(tmp_path)
    shard = _shard_file(tmp_path / "t4")
    with open(shard, "r+b") as f:
        f.seek(os.path.getsize(shard) // 3)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    ok, problems = manifest.verify_tag(str(tmp_path / "t4"))
    assert not ok and "crc32" in problems[0]
    metrics = ResilienceMetrics()
    _assert_falls_back_to_t2(tmp_path, want, metrics=metrics)
    assert metrics.verify_failures == 1 and metrics.fallbacks == 1


def test_truncated_shard_detected_even_in_cheap_size_mode(tmp_path):
    _, want = _make_ckpts(tmp_path)
    shard = _shard_file(tmp_path / "t4")
    with open(shard, "r+b") as f:
        f.truncate(os.path.getsize(shard) // 2)
    _assert_falls_back_to_t2(tmp_path, want, verify="size")


def test_size_mode_misses_bitflips_full_mode_catches(tmp_path):
    """Documents the cheap-mode contract: size-only verification passes a
    same-size bit flip; full CRC mode rejects it."""
    _make_ckpts(tmp_path)
    shard = _shard_file(tmp_path / "t4")
    with open(shard, "r+b") as f:
        f.seek(os.path.getsize(shard) // 3)
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    assert manifest.verify_tag(str(tmp_path / "t4"), mode="size")[0]
    assert not manifest.verify_tag(str(tmp_path / "t4"), mode="full")[0]


def test_chaos_corrupt_action_models_post_write_bitrot(tmp_path):
    """``corrupt_shard_bytes`` fires AFTER the checksum is recorded, so
    the save 'succeeds' silently — the manifest must catch it at load."""
    eng, want = _make_ckpts(tmp_path, steps=(2,))
    eng.train_micro_batch(*CS.batch_fn(2))
    with chaos.inject("corrupt_shard_bytes"):  # default action: corrupt
        eng.save_checkpoint(str(tmp_path), tag="t3")  # completes normally
    assert manifest.read_latest(str(tmp_path)) == "t3"
    ok, problems = manifest.verify_tag(str(tmp_path / "t3"))
    assert not ok and any("crc32" in p for p in problems)
    _assert_falls_back_to_t2(tmp_path, want)


def test_missing_manifest_falls_back_when_verified_tags_exist(tmp_path):
    _, want = _make_ckpts(tmp_path)
    os.remove(tmp_path / "t4" / "manifest.json")
    _assert_falls_back_to_t2(tmp_path, want)


def test_explicit_premanifest_tag_loads_amid_manifested_tags(tmp_path):
    """A committed tag always has a manifest, so a missing one means a
    pre-manifest checkpoint: an EXPLICIT request for it must load
    (unverified, warned) even when newer manifested tags exist."""
    _, want = _make_ckpts(tmp_path)
    os.remove(tmp_path / "t2" / "manifest.json")
    fresh, path, _ = _load_fresh(tmp_path, tag="t2")
    assert path is not None and path.endswith("t2")
    got = _flat(fresh.state["master"])
    for k in want[2]:
        assert np.array_equal(got[k], want[2][k])


def test_pure_premanifest_checkpoint_still_loads(tmp_path):
    """Legacy policy: when NO tag anywhere has a manifest (a checkpoint
    dir written before manifests existed), load proceeds unverified."""
    _, want = _make_ckpts(tmp_path, steps=(2,))
    os.remove(tmp_path / "t2" / "manifest.json")
    fresh, path, _ = _load_fresh(tmp_path)
    assert path is not None and path.endswith("t2")
    got = _flat(fresh.state["master"])
    for k in want[2]:
        assert np.array_equal(got[k], want[2][k])


def test_stale_latest_pointing_at_deleted_tag(tmp_path):
    _, want = _make_ckpts(tmp_path)
    shutil.rmtree(tmp_path / "t4")
    assert manifest.read_latest(str(tmp_path)) == "t4"  # stale on purpose
    _assert_falls_back_to_t2(tmp_path, want)


def test_missing_shard_file_detected(tmp_path):
    _, want = _make_ckpts(tmp_path)
    os.remove(_shard_file(tmp_path / "t4"))
    ok, problems = manifest.verify_tag(str(tmp_path / "t4"))
    assert not ok and "file missing" in problems[0]
    _assert_falls_back_to_t2(tmp_path, want)


def test_missing_shard_index_falls_back_via_load_error(tmp_path):
    """A shard whose ``__index__`` entry is gone but whose checksum is
    'valid' (rewritten + re-manifested) passes verification yet fails to
    parse — the load-error path must fall back, not crash."""
    _, want = _make_ckpts(tmp_path)
    shard = _shard_file(tmp_path / "t4")
    with np.load(shard, allow_pickle=False) as z:
        payload = {k: z[k] for k in z.files if k != "__index__"}
    np.savez(shard, **payload)
    manifest.write_sidecars(str(tmp_path / "t4"), [shard])
    manifest.build_manifest(str(tmp_path / "t4"), "t4", step=4)
    assert manifest.verify_tag(str(tmp_path / "t4"))[0]  # CRC says fine
    _assert_falls_back_to_t2(tmp_path, want)


def test_explicit_tag_never_falls_back_forward(tmp_path):
    """Asking for an old tag must not silently hand back a NEWER one."""
    _, _ = _make_ckpts(tmp_path)
    shard = _shard_file(tmp_path / "t2")
    with open(shard, "r+b") as f:
        f.truncate(os.path.getsize(shard) // 2)
    eng = CS.MiniEngine(seed=1)
    before = _flat(eng.state["master"])
    path, cs = eng.load_checkpoint(str(tmp_path), tag="t2")
    assert path is None and cs == {}
    after = _flat(eng.state["master"])
    for k in before:  # engine state untouched by the failed load
        assert np.array_equal(before[k], after[k])


def test_explicit_missing_tag_does_not_jump_forward(tmp_path):
    """Requested tag's directory is GONE (so its manifest step is
    unknowable): the step parsed from the tag name must still prevent a
    silent jump to a newer tag."""
    _, _ = _make_ckpts(tmp_path)
    shutil.rmtree(tmp_path / "t2")
    eng = CS.MiniEngine(seed=1)
    path, cs = eng.load_checkpoint(str(tmp_path), tag="t2")
    assert path is None and cs == {}  # t4 is newer: refused


def test_resave_existing_tag_never_leaves_zero_copies(tmp_path):
    """Re-saving an existing tag keeps a loadable copy at every instant:
    the old dir moves ASIDE (a fallback candidate) instead of being
    deleted before the rename, and the aside is swept after commit."""
    eng, _ = _make_ckpts(tmp_path, steps=(2,))
    eng.train_micro_batch(*CS.batch_fn(2))
    eng.save_checkpoint(str(tmp_path), tag="t2")  # overwrite same tag
    assert not (tmp_path / "t2.old").exists()     # aside swept post-commit
    ok, problems = manifest.verify_tag(str(tmp_path / "t2"))
    assert ok, problems
    want = _flat(eng.state["master"])
    fresh, path, _ = _load_fresh(tmp_path, tag="t2")
    got = _flat(fresh.state["master"])
    for k in want:  # the NEW (3-step) copy won
        assert np.array_equal(got[k], want[k]), k


def test_empty_dir_and_no_latest(tmp_path):
    eng = CS.MiniEngine(seed=0)
    path, cs = eng.load_checkpoint(str(tmp_path))
    assert path is None and cs == {}


# --------------------------------------------------------------------- #
# AsyncCheckpointEngine: bounded pool + explicit .npz suffix contract
# --------------------------------------------------------------------- #
def test_async_engine_pool_is_bounded_and_suffix_explicit(tmp_path):
    ce = AsyncCheckpointEngine(max_workers=2)
    payload = {"a": np.arange(6, dtype=np.float32)}
    for i in range(8):
        ce.save(payload, str(tmp_path / f"f{i}"))  # note: NO .npz suffix
    assert ce.commit("t")
    # 8 writes, but never more than max_workers threads — and DAEMON
    # ones, so a wedged write can't block interpreter exit
    assert len(ce._workers) == 2
    assert all(t.daemon for t in ce._workers)
    # np.savez appended .npz; load with the SAME suffixless path agrees
    for i in range(8):
        assert os.path.exists(tmp_path / f"f{i}.npz")
        got = ce.load(str(tmp_path / f"f{i}"))
        np.testing.assert_array_equal(got["a"], payload["a"])
    with pytest.raises(ValueError):
        AsyncCheckpointEngine(max_workers=0)


def test_async_engine_surfaces_write_errors_at_commit(tmp_path):
    ce = AsyncCheckpointEngine(max_workers=2)
    ce.save({"a": np.zeros(2)}, str(tmp_path / "missing_dir" / "x"))
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        ce.commit("t")
    # the failed batch was drained; the engine is reusable
    ce.save({"a": np.zeros(2)}, str(tmp_path / "ok"))
    assert ce.commit("t2")


def test_async_engine_end_to_end_with_manifest(tmp_path):
    eng = CS.MiniEngine(seed=0)
    eng.checkpoint_engine = AsyncCheckpointEngine(max_workers=2)
    for s in range(3):
        eng.train_micro_batch(*CS.batch_fn(s))
    eng.save_checkpoint(str(tmp_path), tag="a")
    ok, problems = manifest.verify_tag(str(tmp_path / "a"))
    assert ok, problems
    want = _flat(eng.state["master"])
    fresh, path, _ = _load_fresh(tmp_path, tag="a")
    got = _flat(fresh.state["master"])
    for k in want:
        assert np.array_equal(got[k], want[k])


# --------------------------------------------------------------------- #
# ResilientTrainLoop: retention, sentinel, auto-resume
# --------------------------------------------------------------------- #
class FakeEngine:
    """Pure-python engine for loop-logic tests: 'weights' accumulate the
    batch value, 'loss' IS the batch value, checkpoints are in-memory."""

    def __init__(self):
        self.weights = 0.0
        self.trained = []
        self.global_steps = 0
        self._store = {}

    def train_micro_batch(self, value):
        self.weights += value
        self.trained.append(value)
        self.global_steps += 1
        return value

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        client_state = dict(client_state or {})
        # mimic the real DeepSpeedEngine, which merges ITS OWN top-level
        # keys into client_state (runtime/engine.py save_checkpoint) —
        # including an int "skipped_steps" counter that must not collide
        # with the loop's state
        client_state.update({"global_steps": self.global_steps,
                             "skipped_steps": 0})
        self._store[tag] = (self.weights, self.global_steps, client_state)
        return True

    def load_checkpoint(self, load_dir, tag=None):
        if not self._store:
            return None, {}
        if tag is None:
            tag = max(self._store, key=lambda t: (
                self._store[t][2].get("resilience") or {}).get(
                    "loop_step", 0))
        self.weights, self.global_steps, client_state = self._store[tag]
        return tag, client_state


def test_retention_keep_last_and_keep_every(tmp_path):
    eng = CS.MiniEngine(seed=0)
    loop = ResilientTrainLoop(eng, CS.batch_fn, str(tmp_path),
                              save_interval=2, keep_last=2, keep_every=6)
    loop.run(12)
    tags = sorted(d for d in os.listdir(tmp_path)
                  if (tmp_path / d).is_dir())
    # last 2 (10, 12) + every 6th (6, 12) + latest (12)
    assert tags == ["global_step10", "global_step12", "global_step6"]
    assert loop.metrics.gc_deleted_tags > 0
    assert manifest.read_latest(str(tmp_path)) == "global_step12"
    with pytest.raises(ValueError):
        apply_retention(str(tmp_path), keep_last=0)


def test_sentinel_nan_rolls_back_and_skips_window(tmp_path):
    eng = FakeEngine()
    bad_step = 7

    def data(step):
        return float("nan") if step == bad_step else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=3)
    final = loop.run(10)
    assert final == 10
    assert loop.metrics.rollbacks == 1
    assert loop.metrics.skipped_steps == 1
    assert bad_step in loop._skipped
    # 10 steps minus the skipped one; the NaN update was rolled back
    assert eng.weights == 9.0
    # skipped steps persist through checkpoints for future replays,
    # namespaced so the engine's own top-level keys can't clobber them
    _, _, cs = eng._store["global_step9"]
    assert cs["resilience"]["skipped_steps"] == [bad_step]
    assert cs["skipped_steps"] == 0  # the engine's counter, untouched


def test_sentinel_loss_spike_rolls_back(tmp_path):
    eng = FakeEngine()

    def data(step):
        return 100.0 if step == 10 else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=4,
                              spike_factor=4.0)
    final = loop.run(12)
    assert final == 12
    assert loop.metrics.rollbacks == 1
    assert eng.weights == 11.0  # the 100.0 update was rolled back + skipped


def test_sentinel_arms_with_small_spike_window(tmp_path):
    """A spike_window smaller than the default min-history must still
    arm the spike test (regression: hardcoded >= 8 sample gate)."""
    eng = FakeEngine()

    def data(step):
        return 100.0 if step == 5 else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=4,
                              spike_factor=4.0, spike_window=4)
    assert loop.run(8) == 8
    assert loop.metrics.rollbacks == 1
    assert eng.weights == 7.0


def test_sentinel_gives_up_after_max_rollbacks(tmp_path):
    eng = FakeEngine()

    def data(step):
        return float("nan") if step >= 4 else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=2,
                              max_rollbacks=2)
    with pytest.raises(RuntimeError, match="rollbacks without"):
        loop.run(10)


def test_skip_landing_on_save_boundary_still_checkpoints(tmp_path):
    """A skipped step that advances onto a save boundary must still
    commit — otherwise the checkpoint gap silently doubles."""
    eng = FakeEngine()

    def data(step):
        return float("nan") if step == 1 else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=2)
    # step 1 goes NaN with nothing to roll back to -> marked skipped;
    # the skip advances 1 -> 2, landing exactly on the boundary
    assert loop.run(4) == 4
    assert "global_step2" in eng._store
    assert "global_step4" in eng._store


def test_nan_before_any_checkpoint_skips_without_rollback(tmp_path):
    eng = FakeEngine()

    def data(step):
        return float("nan") if step == 1 else 1.0

    loop = ResilientTrainLoop(eng, data, str(tmp_path), save_interval=50)
    assert loop.run(4) == 4
    assert loop.metrics.rollbacks == 1  # attempted; nothing to restore
    assert 1 in loop._skipped


def test_auto_resume_bit_exact_and_iterator_fast_forward(tmp_path):
    # uninterrupted reference
    ref = CS.MiniEngine(seed=0)
    for s in range(12):
        ref.train_micro_batch(*CS.batch_fn(s))
    want = _flat(ref.state["master"])

    # phase 1: train to 6 with checkpoints
    eng1 = CS.MiniEngine(seed=0)
    ResilientTrainLoop(eng1, CS.batch_fn, str(tmp_path),
                       save_interval=3).run(6)
    # phase 2: fresh engine + a plain ITERATOR data source — auto_resume
    # must fast-forward it by consuming the first 6 batches
    eng2 = CS.MiniEngine(seed=0)
    data = iter([CS.batch_fn(s) for s in range(12)])
    loop2 = ResilientTrainLoop(eng2, data, str(tmp_path), save_interval=3)
    assert loop2.run(12) == 12
    assert loop2.metrics.resumes == 1
    got = _flat(eng2.state["master"])
    for k in want:
        assert np.array_equal(got[k], want[k]), k


def test_loop_rolls_back_through_corrupt_tag(tmp_path):
    """Rollback meets corruption: the newest tag is corrupt, so the
    loader walks back to the previous verified tag and the loop replays
    from there."""
    eng = CS.MiniEngine(seed=0)
    ResilientTrainLoop(eng, CS.batch_fn, str(tmp_path),
                       save_interval=2, keep_last=5).run(6)
    shard = _shard_file(tmp_path / "global_step6")
    with open(shard, "r+b") as f:
        f.truncate(os.path.getsize(shard) // 2)
    eng2 = CS.MiniEngine(seed=0)
    metrics = ResilienceMetrics()
    loop = ResilientTrainLoop(eng2, CS.batch_fn, str(tmp_path),
                              save_interval=2, keep_last=5, metrics=metrics)
    assert loop.run(8) == 8
    assert metrics.resumes == 1 and metrics.verify_failures >= 1
    assert metrics.fallbacks == 1
    ref = CS.MiniEngine(seed=0)
    for s in range(8):
        ref.train_micro_batch(*CS.batch_fn(s))
    want, got = _flat(ref.state["master"]), _flat(eng2.state["master"])
    for k in want:
        assert np.array_equal(got[k], want[k]), k


# --------------------------------------------------------------------- #
# Chaos harness mechanics + metrics export
# --------------------------------------------------------------------- #
def test_chaos_arm_fire_semantics(tmp_path):
    with pytest.raises(ValueError):
        chaos.arm("not_a_point")
    with pytest.raises(ValueError):
        chaos.arm("slow_io", action="explode")
    fault = chaos.arm("slow_io", action="sleep", sleep_s=0.0, after=1,
                      count=2)
    for _ in range(5):
        chaos.fire("slow_io")
    assert fault.hits == 5 and fault.fires == 2  # after=1 skip, count=2 cap
    chaos.disarm("slow_io")
    chaos.fire("slow_io")  # disarmed: no-op
    # corrupt action flips exactly one byte
    p = tmp_path / "blob"
    p.write_bytes(b"\x00" * 64)
    chaos.arm("corrupt_shard_bytes")
    chaos.fire("corrupt_shard_bytes", path=str(p))
    data = p.read_bytes()
    assert len(data) == 64 and sum(b != 0 for b in data) == 1


def test_manifest_crc_and_verify_validation(tmp_path):
    p = tmp_path / "x"
    p.write_bytes(b"hello world")
    import zlib

    assert manifest.file_crc32(str(p)) == zlib.crc32(b"hello world")
    with pytest.raises(ValueError):
        manifest.verify_tag(str(tmp_path), mode="paranoid")


def test_resilience_metrics_export_wallclock_csv(tmp_path):
    from deepspeed_tpu.monitor.monitor import CSVMonitor

    mon = CSVMonitor(types.SimpleNamespace(
        enabled=True, output_path=str(tmp_path), job_name="rz"))
    mon.enabled = True
    metrics = ResilienceMetrics(monitor=mon)
    metrics.record_save(0.25)
    metrics.record_resume("t2", 4)
    metrics.record_rollback(7)
    events = metrics.export(now=123.5)
    names = {n for n, _, _ in events}
    assert {"resilience/saves", "resilience/save_latency_s",
            "resilience/resumes", "resilience/rollbacks",
            "resilience/verify_failures"} <= names
    rows = list(csv.reader(
        (tmp_path / "rz" / "resilience_saves.csv").open()))
    assert rows[1] == ["123.5", "1.0"]


# --------------------------------------------------------------------- #
# The tier-1 smoke (tools/chaos_smoke.py): kill mid-save, restart,
# auto-resume, bit-exact continuation
# --------------------------------------------------------------------- #
def test_chaos_smoke_tool(tmp_path):
    snap = CS.run_smoke(str(tmp_path))
    assert snap["resumes"] == 1
    assert snap["resumed_from"] == f"global_step{CS.SAVE_INTERVAL}"
    assert snap["resumed_final_loss"] == snap["ref_final_loss"]
