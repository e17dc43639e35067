"""Supervisor smoke (~25 s CPU): prove the detect → kill → resize → resume
loop end-to-end with 2 subprocess workers.

Two variants over the same worker program (a single-device ``MiniEngine``
training loop under :class:`ResilientTrainLoop`):

**crash** — the parent SIGKILLs worker 0 mid-step (after at least one
checkpoint has committed).  The supervisor sees the nonzero exit, tears
down the sibling, backs off, relaunches both; each worker
``auto_resume()``s from its last verified tag and the final master
weights, optimizer state, and post-resume loss curve are bit-exact
against an uninterrupted in-process reference run.

**hang** — worker 0 is launched with ``DS_CHAOS=heartbeat_stall`` armed:
after a few beats its heartbeat goes silent while the process keeps
computing (the wedged-collective signature).  The supervisor must detect
the hang within 2× the heartbeat interval, capture a faulthandler stack
dump from the stuck worker BEFORE killing it, then restart and resume to
a bit-exact finish.

Wired into tier-1 via ``tests/unit/test_supervisor.py`` (behind a hard
subprocess timeout).  Run standalone::

    JAX_PLATFORMS=cpu python tools/supervisor_smoke.py
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import sys
import tempfile
import time

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))

_spec = importlib.util.spec_from_file_location(
    "chaos_smoke", os.path.join(_TOOLS, "chaos_smoke.py"))
CS = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(CS)

CRASH_STEPS = 48          # several seconds of stepping: launch-time skew
                          # between the workers can never outrun the kill
HANG_STEPS = 120          # the post-stall runway (>= 112 * STEP_SLEEP_S =
                          # 5.6 s by sleep floor alone) must comfortably
                          # exceed the hang timeout whatever the save
                          # latency, or the worker finishes first
SAVE_INTERVAL = 4
STEP_SLEEP_S = 0.05       # slows the worker so faults land mid-run
# A save+retention pass (~0.5-1.5 s on this FS under load) runs between
# beats, so the hang timeout must clear it with wide margin; detection
# still lands within the 2x-interval acceptance bound
# (timeout + poll <= 2 * interval).
HB_INTERVAL_S = 2.0
HANG_TIMEOUT_S = 3.6
POLL_S = 0.2


# --------------------------------------------------------------------- #
# Worker program (one per "host"; no cross-worker comm — the supervision
# contract is what's under test, not the collectives)
# --------------------------------------------------------------------- #
def run_worker(workdir: str, total_steps: int) -> int:
    from deepspeed_tpu.resilience import ResilientTrainLoop

    seed = int(os.environ.get("DS_SMOKE_SEED", "0"))
    engine = CS.MiniEngine(seed=seed)

    def slow_batch_fn(step: int):
        time.sleep(STEP_SLEEP_S)
        return CS.batch_fn(step)

    loop = ResilientTrainLoop(engine, slow_batch_fn, workdir,
                              save_interval=SAVE_INTERVAL, keep_last=2)
    start_step = loop.auto_resume()
    resumed_wall = time.time()
    loop.run(total_steps, auto_resume=False)

    import numpy as np

    flat = {}
    for name in ("master", "opt"):
        for k, v in CS._flat(engine.state[name]).items():
            flat[f"{name}/{k}"] = v
    np.savez(os.path.join(workdir, "final_state.npz"), **flat)
    with open(os.path.join(workdir, "result.json"), "w") as f:
        json.dump({"start_step": start_step,
                   "resumed_wall": resumed_wall,
                   "losses": engine.losses,
                   "pid": os.getpid()}, f)
    return 0


def _reference(seed: int, total_steps: int):
    """Uninterrupted in-process run: the bit-exactness oracle."""
    engine = CS.MiniEngine(seed=seed)
    for step in range(total_steps):
        engine.train_micro_batch(*CS.batch_fn(step))
    flat = {}
    for name in ("master", "opt"):
        for k, v in CS._flat(engine.state[name]).items():
            flat[f"{name}/{k}"] = v
    return flat, engine.losses


# --------------------------------------------------------------------- #
# Variants
# --------------------------------------------------------------------- #
def _make_supervisor(base: str, variant: str, total_steps: int,
                     worker0_env):
    from deepspeed_tpu.resilience import (BackoffPolicy, JobSupervisor,
                                          WorkerSpec)

    hosts = ["w0", "w1"]

    def spec_fn(current_hosts, attempt):
        specs = []
        for i, host in enumerate(current_hosts):
            workdir = os.path.join(base, variant, host)
            os.makedirs(workdir, exist_ok=True)
            env = {"DS_SMOKE_SEED": host[1:], "JAX_PLATFORMS": "cpu"}
            if host == "w0" and attempt == 0:
                env.update(worker0_env)
            specs.append(WorkerSpec(
                host=host,
                cmd=[sys.executable, os.path.abspath(__file__), "--worker",
                     workdir, str(total_steps)],
                env=env))
        return specs

    return JobSupervisor(
        spec_fn, hosts,
        run_dir=os.path.join(base, variant, "supervisor"),
        heartbeat_interval_s=HB_INTERVAL_S,
        hang_timeout_s=HANG_TIMEOUT_S,
        poll_s=POLL_S,
        term_grace_s=5.0,
        dump_grace_s=2.0,
        backoff=BackoffPolicy(base_s=0.1, jitter=0.0),
        max_restarts=3,
        blacklist_after=3)


def _check_worker_results(base: str, variant: str, total_steps: int,
                          require_resume=("w0", "w1")) -> dict:
    """Workers finished bit-exactly; those in ``require_resume`` must have
    auto-resumed from a checkpoint rather than restarted fresh."""
    import numpy as np

    out = {}
    for host, seed in (("w0", 0), ("w1", 1)):
        workdir = os.path.join(base, variant, host)
        with open(os.path.join(workdir, "result.json")) as f:
            result = json.load(f)
        if host in require_resume:
            assert result["start_step"] > 0, \
                f"{variant}/{host}: restarted fresh instead of auto-resuming"
        assert result["start_step"] % SAVE_INTERVAL == 0, result["start_step"]
        ref_state, ref_losses = _reference(seed, total_steps)
        got = np.load(os.path.join(workdir, "final_state.npz"))
        assert set(got.files) == set(ref_state), \
            (variant, host, set(got.files) ^ set(ref_state))
        for k in ref_state:
            assert np.array_equal(ref_state[k], got[k]), \
                f"{variant}/{host}: {k} diverged after resume"
        # the resumed incarnation's loss curve matches the uninterrupted
        # run from the resume point on — bit-exact continuation
        assert result["losses"] == ref_losses[result["start_step"]:], \
            f"{variant}/{host}: post-resume loss curve diverged"
        out[host] = result
    return out


def run_crash_variant(base: str) -> dict:
    """SIGKILL worker 0 mid-step; supervisor relaunches; bit-exact."""
    from deepspeed_tpu.resilience import read_heartbeat

    sup = _make_supervisor(base, "crash", CRASH_STEPS, worker0_env={})
    sup.start()
    handles = list(sup.handles)
    victim = handles[0]
    # wait until BOTH workers are mid-run with >= 1 checkpoint committed
    # (the sibling gets torn down too and must also be able to resume)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        steps = [read_heartbeat(h.heartbeat_file).step for h in handles]
        if all(s is not None and s >= SAVE_INTERVAL + 2 for s in steps):
            break
        time.sleep(0.02)
    else:
        raise AssertionError("workers never reached the kill step")
    assert victim.proc.poll() is None, \
        "victim finished before the mid-step kill — raise CRASH_STEPS"
    os.kill(victim.pid, signal.SIGKILL)
    t_kill = time.time()

    rc = sup.wait(timeout=180)
    assert rc == 0, (rc, sup.error, sup.events)
    assert sup.metrics.restarts == 1 and sup.metrics.restart_crash == 1, \
        sup.metrics.snapshot()
    restart = [e for e in sup.events if e["event"] == "restart"][0]
    assert restart["reason"] == "crash", restart
    assert (restart["world_before"], restart["world_after"]) == (2, 2), \
        restart
    results = _check_worker_results(base, "crash", CRASH_STEPS)
    detect = [e for e in sup.events if e["event"] == "crash_detected"][0]
    return {
        "crash_detect_latency_s": round(detect["t"] - t_kill, 3),
        "crash_restart_to_resume_s": round(
            results["w0"]["resumed_wall"] - detect["t"], 3),
        "crash_resume_step": results["w0"]["start_step"],
    }


def run_hang_variant(base: str) -> dict:
    """heartbeat_stall on worker 0: detect within 2x the interval, dump
    the stuck worker's stacks, restart, resume bit-exactly."""
    # after=8: the stall begins right after worker 0's first save (step 4)
    # commits, leaving the longest possible post-stall runway before the
    # worker would finish on its own
    sup = _make_supervisor(
        base, "hang", HANG_STEPS,
        worker0_env={"DS_CHAOS": "heartbeat_stall:after=8,count=0"})
    rc = sup.run(timeout=240)
    assert rc == 0, (rc, sup.error, sup.events)
    assert sup.metrics.restarts == 1 and sup.metrics.restart_hang == 1, \
        sup.metrics.snapshot()
    hang = [e for e in sup.events if e["event"] == "hang_detected"][0]
    assert hang["host"] == "w0", hang
    # the acceptance bound: a stalled heartbeat is flagged within 2x the
    # beat interval (hang_timeout + one poll < 2x interval)
    assert hang["age_s"] <= 2 * HB_INTERVAL_S, hang
    dumps = sup.dumps.get("w0", [])
    assert dumps and "File" in dumps[0], \
        f"no stack dump captured before the kill: {sup.events}"
    # w1's resume depends on launch-time skew, so only the hung worker's
    # resume is asserted; bit-exactness is asserted for both
    results = _check_worker_results(base, "hang", HANG_STEPS,
                                    require_resume=("w0",))
    detect_t = hang["t"]
    return {
        "hang_detect_age_s": round(hang["age_s"], 3),
        "hang_restart_to_resume_s": round(
            results["w0"]["resumed_wall"] - detect_t, 3),
        "hang_dump_chars": len(dumps[0]),
    }


def run_smoke(tmpdir: str | None = None) -> dict:
    if tmpdir is None:
        tmpdir = tempfile.mkdtemp(prefix="supervisor_smoke_")
    snap = {}
    snap.update(run_crash_variant(tmpdir))
    snap.update(run_hang_variant(tmpdir))
    return snap


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        return run_worker(sys.argv[2], int(sys.argv[3]))
    t0 = time.monotonic()
    snap = run_smoke()
    snap["wall_s"] = round(time.monotonic() - t0, 2)
    print(json.dumps({"supervisor_smoke": "ok", **snap}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
