"""CPU rehearsal of every cell at tiny sizes (control flow, counts,
correctness against the plain reference), behind the test-only entry
``run_cell(..., allow_cpu=True)``; and the real command line refusing to run
without a TPU.  No number from here is a device metric."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.lib import spec
from benchmark.tests.rehearsal_sizes import TINY


@pytest.mark.parametrize("cell", sorted(TINY))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearses_on_cpu(cell, trace):
    if trace and cell.startswith("serve") and "chat" not in cell:
        pytest.skip("one traced serving rehearsal is enough")
    out = run.run_cell(cell, 3, 2.0, trace, overrides=TINY[cell],
                       allow_cpu=True)
    facts = out.pop("_facts")
    assert out["rehearsal"] and out["overrides"]
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert facts["programs_built_window"] == 0
    json.dumps(out)                          # the line is serialisable
    b = spec.benchmark_spec()
    if not trace:
        want = {m["name"] for m in spec.metrics_for(b, "end_to_end", cell)}
        assert set(out["metrics"]) == want
        assert all(v["value"] > 0 for v in out["metrics"].values())
    else:
        # nothing ran on a device: device metrics are left out, not zero
        assert "busy_s" not in out["device"] and "breakdown" not in out
        built = [v["value"] for k, v in out["metrics"].items()
                 if k.endswith("programs_built_window")]
        assert built == [0]
        if cell.startswith("serve"):
            live = [v["value"] for k, v in out["metrics"].items()
                    if k.endswith("kv_live_pct")]
            assert len(live) == 1 and 0 < live[0] <= 100
        for name in ("device_idle_pct", "chat_device_idle_pct",
                     "train_device_idle_pct", "paged_attn_ms_tick",
                     "chat_paged_attn_ms_tick", "attn_kernel_ms_step",
                     "mfu_pct"):
            assert name not in out["metrics"]


def test_command_line_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", "train-gpt2large-d64-s1k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.CHECKOUT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_knee_sweep_rehearses_on_cpu():
    from benchmark.tools import knee_sweep

    cell = "serve-mistral7b-chat-steady"
    table = knee_sweep.main(
        ["--cell", cell, "--tag", "_rehearsal", "--seconds", "2",
         "--seeds", "3,4", "2.0", "4.0"], allow_cpu=True,
        overrides=TINY[cell])
    assert [r["rate_per_s"] for r in table] == [2.0, 4.0][:len(table)]
    first = table[0]
    assert first["windows"] == 2 and first["due"] == 8    # 2 x round(2 x 2)
    # whether a rate is "sustained" hangs on the host's speed at these counts
    # (one request unfinished at a 2 s window's end is enough to say no)
    assert first["failed"] == 0 and first["sustained"] in (True, False)
    assert all(v is not None and v > 0 for v in first["kv_live_pct"])
