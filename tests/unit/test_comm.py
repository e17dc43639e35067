"""Collective facade tests over the 8-device CPU mesh (reference:
tests/unit/comm/test_dist.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import deepspeed_tpu.comm as dist
from deepspeed_tpu.parallel import groups


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@pytest.fixture
def mesh():
    return groups.initialize_mesh(data_parallel_size=8).mesh


def test_all_reduce_sum(mesh):
    x = jnp.arange(8.0)

    f = _shard_map(lambda v: dist.all_reduce(v, group="data"),
                   mesh, in_specs=P("data"), out_specs=P("data"))
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 28.0))


def test_all_gather(mesh):
    x = jnp.arange(8.0).reshape(8, 1)

    f = _shard_map(lambda v: dist.all_gather(v, group="data", axis=0),
                   mesh, in_specs=P("data", None), out_specs=P(None, None))
    out = jax.jit(f)(x)
    assert out.shape == (8, 1)
    np.testing.assert_allclose(np.asarray(out).ravel(), np.arange(8.0))


def test_reduce_scatter(mesh):
    # each shard holds the full vector; reduce_scatter sums and splits
    x = jnp.ones((8, 8))

    f = _shard_map(lambda v: dist.reduce_scatter(v, group="data", axis=0),
                   mesh, in_specs=P(None, None), out_specs=P("data", None))
    out = jax.jit(f)(x)
    assert out.shape == (8, 8)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 8), 8.0))


def test_all_to_all(mesh):
    groups.reset()
    topo = groups.initialize_mesh(data_parallel_size=1, sequence_parallel_size=8)
    x = jnp.arange(64.0).reshape(8, 8)

    f = _shard_map(
        lambda v: dist.all_to_all_single(v, group="sp", split_axis=1,
                                         concat_axis=0),
        topo.mesh, in_specs=P("seq", None), out_specs=P(None, "seq"))
    out = jax.jit(f)(x)
    # all_to_all of a row-sharded matrix splitting columns = transpose of
    # block layout; global result must be a permutation with same content
    assert out.shape == (8, 8)
    np.testing.assert_allclose(np.sort(np.asarray(out).ravel()),
                               np.arange(64.0))


def test_broadcast(mesh):
    x = jnp.arange(8.0)

    f = _shard_map(lambda v: dist.broadcast(v, src=3, group="data"),
                   mesh, in_specs=P("data"), out_specs=P("data"))
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 3.0))


def test_ppermute_ring(mesh):
    groups.reset()
    topo = groups.initialize_mesh(pipe_parallel_size=8, data_parallel_size=1)
    x = jnp.arange(8.0)
    perm = [(i, (i + 1) % 8) for i in range(8)]

    f = _shard_map(lambda v: dist.ppermute(v, perm, group="pp"),
                   topo.mesh, in_specs=P("pipe"), out_specs=P("pipe"))
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_axis_index_multiaxis():
    groups.reset()
    topo = groups.initialize_mesh(data_parallel_size=4, model_parallel_size=2)

    f = _shard_map(lambda v: v * 0 + dist.axis_index(("data", "model")),
                   topo.mesh, in_specs=P(("data", "model")),
                   out_specs=P(("data", "model")))
    out = jax.jit(f)(jnp.zeros(8, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))


def test_comms_logger(mesh):
    dist.configure(enabled=True)
    x = jnp.arange(8.0)
    f = _shard_map(lambda v: dist.all_reduce(v, group="data"),
                   mesh, in_specs=P("data"), out_specs=P("data"))
    jax.jit(f)(x)
    summary = dist.log_summary()
    assert "all_reduce" in summary
    dist.configure(enabled=False)


def test_host_api():
    dist.init_distributed()
    assert dist.get_rank() == 0
    assert dist.get_world_size() == 1
    dist.barrier()


# ------------------------------------------------------------------ #
# barrier(timeout=) + the uninitialized-collective guard
# ------------------------------------------------------------------ #
def test_barrier_timeout_raises_instead_of_deadlocking(monkeypatch):
    import time

    from deepspeed_tpu.comm import comm as comm_mod

    # a peer that never arrives: the underlying sync blocks "forever"
    monkeypatch.setattr(comm_mod, "_sync_global",
                        lambda tag: time.sleep(30))
    t0 = time.monotonic()
    with pytest.raises(dist.CommTimeoutError, match="timed out"):
        dist.barrier(timeout=0.2, tag="test.barrier")
    assert time.monotonic() - t0 < 5.0       # raised promptly, no deadlock
    with pytest.raises(ValueError, match="timeout must be > 0"):
        dist.barrier(timeout=0.0)


def test_barrier_timeout_passes_when_sync_completes(monkeypatch):
    from deepspeed_tpu.comm import comm as comm_mod

    calls = []
    monkeypatch.setattr(comm_mod, "_sync_global", calls.append)
    dist.barrier(timeout=5.0, tag="test.fast")
    assert calls == ["test.fast"]


def test_barrier_timeout_propagates_sync_errors(monkeypatch):
    from deepspeed_tpu.comm import comm as comm_mod

    def _boom(tag):
        raise RuntimeError("peer went away")

    monkeypatch.setattr(comm_mod, "_sync_global", _boom)
    with pytest.raises(RuntimeError, match="peer went away"):
        dist.barrier(timeout=5.0)


def test_collective_outside_mesh_names_init_distributed():
    """An eager collective (no mesh axes bound) must fail with an
    actionable error naming init_distributed, not jax's bare
    ``NameError: unbound axis name``."""
    with pytest.raises(RuntimeError, match="init_distributed"):
        dist.all_reduce(jnp.arange(4.0), group="data")
    with pytest.raises(RuntimeError, match="no mesh axis"):
        dist.all_gather(jnp.arange(4.0), group="data")
    with pytest.raises(RuntimeError, match="shard_map"):
        dist.reduce_scatter(jnp.arange(8.0), group="data")


def test_slurm_first_host_compressed_nodelists():
    """mpi_discovery must resolve rank-0's host from compressed SLURM
    nodelists (ADVICE r3: node[01-04] is the common production form)."""
    from deepspeed_tpu.comm.comm import _slurm_first_host

    assert _slurm_first_host("node01,node02") == "node01"
    assert _slurm_first_host("node[01-04]") == "node01"
    assert _slurm_first_host("gpu[003,007-009]") == "gpu003"
    assert _slurm_first_host("tpu-host[12-14],other[1-2]") == "tpu-host12"
    assert _slurm_first_host("") == ""
