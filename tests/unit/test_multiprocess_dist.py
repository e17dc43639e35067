"""Real 2-process ``jax.distributed`` rendezvous through the repo's own
launcher env protocol (reference pattern: ``tests/unit/common.py:107``
``DistributedExec`` spawns real N-process groups for comm tests; the
virtual 8-device mesh used everywhere else never crosses a process
boundary).

Each worker is a fresh Python process with the exact env the node
launcher exports (``launcher/launch.py:83`` — COORDINATOR_ADDRESS /
WORLD_SIZE / RANK / LOCAL_RANK), pinned to CPU with 2 local virtual
devices, calling ``comm.init_distributed`` -> one cross-process
collective -> one data-parallel engine train step over the 4-device
global mesh.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
sys.path.insert(0, os.environ["DS_REPO_ROOT"])

from deepspeed_tpu import comm

comm.init_distributed(verbose=False)

import jax
import jax.numpy as jnp
import numpy as np

assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()
assert comm.get_rank() == int(os.environ["RANK"])
assert comm.get_world_size() == 2

# one cross-process collective: allgather of the process index
from jax.experimental import multihost_utils

gathered = multihost_utils.process_allgather(
    jnp.asarray([float(jax.process_index())]))
assert sorted(np.asarray(gathered).ravel().tolist()) == [0.0, 1.0], gathered

# one engine step over the global 4-device mesh (data-parallel)
import deepspeed_tpu
from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

cfg = GPT2Config.tiny(dtype=jnp.float32)
engine, _, _, _ = deepspeed_tpu.initialize(
    model=GPT2LMHeadModel(cfg),
    config={"train_micro_batch_size_per_gpu": 4,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1}})
ids = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab_size, size=(4, 8)), jnp.int32)
loss = engine(ids, ids)
engine.backward(loss)
engine.step()
val = float(jax.device_get(loss))
assert np.isfinite(val)
comm.barrier()
print(f"worker {os.environ['RANK']} OK loss={val:.4f}", flush=True)
"""


def test_two_process_rendezvous_and_engine_step(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)

    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               # let the worker pin its own platform/device count
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        env.update({
            "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "WORLD_SIZE": "2",
            "RANK": str(rank),
            "LOCAL_RANK": str(rank),
            "DS_REPO_ROOT": repo_root,
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))

    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"worker {rank} OK" in out, out
