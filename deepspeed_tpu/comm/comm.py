"""Communication facade (reference: deepspeed/comm/comm.py:222-520 module-level
collectives, ``init_distributed:604``).

Two tiers, matching how TPU programs are actually structured:

* **In-graph** collectives — ``all_reduce``/``all_gather``/``reduce_scatter``/
  ``all_to_all_single``/``broadcast``/``send``-style ``ppermute`` — callable
  inside ``shard_map`` regions where mesh axis names are bound. ``group`` is a
  mesh-axis tuple or an alias string ("dp", "tp", "sdp", ...; see
  ``parallel/topology.GROUP_ALIASES``). Every call is recorded by the
  trace-time comms logger (reference ``timed_op`` comm/comm.py:101).

* **Host-level** process coordination — ``init_distributed`` (over
  ``jax.distributed``), ``get_rank``/``get_world_size`` (process index/count),
  ``barrier``. These concern multi-host orchestration; device-level
  communication always goes through the in-graph tier.
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.comm.comms_logging import get_comms_logger
from deepspeed_tpu.comm.xla_backend import ReduceOp, XlaBackend
from deepspeed_tpu.parallel.topology import resolve_group
from deepspeed_tpu.utils.logging import logger

_backend: Optional[XlaBackend] = None
_initialized = False


class CommTimeoutError(RuntimeError):
    """A host-level synchronization point (``barrier(timeout=...)``)
    expired.  The descriptive alternative to deadlocking forever on a
    hung or dead peer — supervisors catch this and restart the group."""


def _get_backend() -> XlaBackend:
    global _backend
    if _backend is None:
        _backend = XlaBackend()
        _backend.init_process_group()
    return _backend


def is_initialized() -> bool:
    return _initialized


def _slurm_first_host(nodelist: str) -> str:
    """First hostname of a SLURM nodelist — rank 0 under block
    distribution.  Handles plain comma lists, `scontrol show hostnames`
    when present, and the simple compressed ``prefix[NN-MM,...]`` form;
    returns '' when the list cannot be resolved."""
    if not nodelist:
        return ""
    # head element at the top level (commas inside [...] are range lists)
    depth, head = 0, nodelist
    for i, c in enumerate(nodelist):
        if c == "[":
            depth += 1
        elif c == "]":
            depth -= 1
        elif c == "," and depth == 0:
            head = nodelist[:i]
            break
    if "[" not in head:
        return head
    import re
    import shutil
    import subprocess

    if shutil.which("scontrol"):
        try:
            r = subprocess.run(["scontrol", "show", "hostnames", nodelist],
                               capture_output=True, text=True, timeout=10)
            if r.returncode == 0 and r.stdout.strip():
                return r.stdout.split()[0]
        except Exception:  # noqa: BLE001 — fall through to the parser
            pass
    m = re.match(r"^([^,\[]+)\[([0-9]+)", head)
    if m:
        return f"{m.group(1)}{m.group(2)}"
    return ""


def mpi_discovery(distributed_port: int = 29500, verbose: bool = True
                  ) -> None:
    """Populate RANK/WORLD_SIZE/LOCAL_RANK from scheduler environments when
    the launcher didn't (reference comm/comm.py:673 ``mpi_discovery`` — it
    broadcasts the master over MPI; here the SLURM / OpenMPI / Intel-MPI
    environment variables carry everything, and the coordinator defaults to
    the scheduler-provided first host)."""
    env = os.environ
    schemes = (
        ("SLURM_PROCID", "SLURM_NTASKS", "SLURM_LOCALID"),
        ("OMPI_COMM_WORLD_RANK", "OMPI_COMM_WORLD_SIZE",
         "OMPI_COMM_WORLD_LOCAL_RANK"),
        ("PMI_RANK", "PMI_SIZE", "MPI_LOCALRANKID"),
    )
    for rank_k, world_k, local_k in schemes:
        if rank_k in env and world_k in env:
            env.setdefault("RANK", env[rank_k])
            env.setdefault("WORLD_SIZE", env[world_k])
            if local_k in env:
                env.setdefault("LOCAL_RANK", env[local_k])
            if "COORDINATOR_ADDRESS" not in env:
                # rank 0's HOST, not the submitting node:
                # SLURM_LAUNCH_NODE_IPADDR is where srun was typed (often
                # a login node with no task). The first entry of the job
                # nodelist is rank 0 under block distribution. Compressed
                # ranges (node[01-04] — the common production form) are
                # expanded via `scontrol show hostnames` when available,
                # falling back to parsing the simple prefix[NN-MM] form;
                # only if both fail is the address left unset so init
                # fails loudly rather than hang on a coordinator nobody
                # can bind.
                nodelist = env.get("SLURM_JOB_NODELIST", "")
                host = _slurm_first_host(nodelist)
                if host:
                    env["COORDINATOR_ADDRESS"] = \
                        f"{host}:{distributed_port}"
            if verbose:
                logger.info(
                    f"mpi_discovery: rank={env['RANK']} "
                    f"world={env['WORLD_SIZE']} (from {rank_k})")
            return


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     distributed_port: int = 29500,
                     verbose: bool = True,
                     timeout=None,
                     init_method: Optional[str] = None,
                     dist_init_required: Optional[bool] = None,
                     config=None,
                     rank: int = -1,
                     world_size: int = -1) -> None:
    """Initialise multi-host coordination (reference comm/comm.py:604).

    Single-process (one TPU VM or CPU sim): nothing to rendezvous; the mesh
    covers all local devices. Multi-host (TPU pod slice): delegates to
    ``jax.distributed.initialize`` which plays the role of the reference's
    ``torch.distributed.init_process_group`` NCCL rendezvous.
    """
    global _initialized
    if _initialized:
        return
    import jax

    if auto_mpi_discovery and "RANK" not in os.environ:
        mpi_discovery(distributed_port=distributed_port, verbose=verbose)
    coord = os.environ.get("COORDINATOR_ADDRESS") or init_method
    n_procs = int(os.environ.get("WORLD_SIZE", world_size if world_size > 0 else 1))
    if coord or n_procs > 1 or dist_init_required:
        kwargs = {}
        if coord:
            kwargs["coordinator_address"] = coord.replace("tcp://", "")
        if n_procs > 1:
            kwargs["num_processes"] = n_procs
        proc_id = int(os.environ.get("RANK", rank if rank >= 0 else 0))
        if "num_processes" in kwargs:
            kwargs["process_id"] = proc_id
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:  # already initialized or single-host
            if verbose:
                logger.warning(f"jax.distributed.initialize skipped: {e}")
    _get_backend()
    _initialized = True
    if verbose:
        # the first touch of the backend: a device that fails to start
        # raises here rather than later under some other name
        logger.info(f"Initialized comm backend=xla "
                    f"processes={get_world_size()} "
                    f"devices={len(jax.devices())}")


def get_rank(group=None) -> int:
    """Host process index (reference rank == per-process identity)."""
    import jax

    return jax.process_index()


def get_world_size(group=None) -> int:
    import jax

    return jax.process_count()


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", 0))


def _sync_global(tag: str) -> None:
    """The blocking cross-host sync (factored out so tests can simulate a
    hung peer without a real multi-process group)."""
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def barrier(group=None, timeout: Optional[float] = None,
            tag: str = "deepspeed_tpu.barrier") -> None:
    """Host-level barrier.  With ``timeout`` (seconds), a peer that never
    arrives raises :class:`CommTimeoutError` instead of deadlocking this
    process at the dispatch level — the failure a job supervisor can act
    on.  The abandoned sync runs out its course on a daemon thread (the
    underlying rendezvous has no cancellation API), so a process that
    chooses to continue after the error must re-synchronize with a fresh
    tag."""
    if timeout is None:
        return _sync_global(tag)
    if timeout <= 0:
        raise ValueError(f"barrier timeout must be > 0, got {timeout}")
    done = threading.Event()
    errs: list = []

    def _run():
        try:
            _sync_global(tag)
        except BaseException as e:  # noqa: BLE001 — surfaced to caller
            errs.append(e)
        finally:
            done.set()

    t = threading.Thread(target=_run, name=f"ds-barrier-{tag}", daemon=True)
    t.start()
    if not done.wait(timeout):
        import jax

        raise CommTimeoutError(
            f"barrier {tag!r} timed out after {timeout}s waiting for "
            f"{jax.process_count()} process(es): a peer is hung or dead "
            "(a supervisor should tear down and restart the worker group; "
            "this process's sync thread is abandoned)")
    if errs:
        raise errs[0]


def destroy_process_group() -> None:
    global _initialized
    _initialized = False


# --------------------------------------------------------------------- #
# In-graph collectives (valid where mesh axis names are bound)
# --------------------------------------------------------------------- #
def _log(op_name: str, tensor, group) -> None:
    lg = get_comms_logger()
    if lg.enabled:
        try:
            nbytes = int(np.prod(tensor.shape)) * tensor.dtype.itemsize
        except Exception:
            nbytes = 0
        lg.append(op_name, nbytes, group=group)


def _dispatch(op_name: str, axes, thunk):
    """Run one collective, translating JAX's bare ``NameError: unbound
    axis name`` — what an eager call outside any mesh context produces —
    into an actionable error that names :func:`init_distributed`.  Inside
    ``shard_map`` (axis names bound) this adds nothing to the hot path
    beyond the try frame."""
    try:
        return thunk()
    except NameError as e:
        if "axis name" not in str(e):
            raise          # a genuine NameError bug, not an unbound axis
        raise RuntimeError(
            f"comm.{op_name}(group={axes!r}) was called where no mesh axis "
            f"is bound ({e}). Collectives are in-graph: call "
            "deepspeed_tpu.init_distributed() first and invoke them inside "
            "the engine's shard_map/mesh context — not eagerly at top "
            "level." + ("" if is_initialized() else
                        " (init_distributed has NOT been called in this "
                        "process.)")) from e


def all_reduce(tensor, op=ReduceOp.SUM, group=None, async_op: bool = False):
    axes = resolve_group(group)
    _log("all_reduce", tensor, axes)
    return _dispatch("all_reduce", axes,
                     lambda: _get_backend().all_reduce(tensor, op=op,
                                                       group=axes))


def inference_all_reduce(tensor, group=None):
    return all_reduce(tensor, op=ReduceOp.SUM, group=group or "tp")


def all_gather(tensor, group=None, axis: int = 0, async_op: bool = False):
    axes = resolve_group(group)
    _log("all_gather", tensor, axes)
    return _dispatch("all_gather", axes,
                     lambda: _get_backend().all_gather(tensor, group=axes,
                                                       axis=axis))


# reference names all_gather_into_tensor / allgather_fn
all_gather_into_tensor = all_gather


def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, axis: int = 0,
                   async_op: bool = False):
    axes = resolve_group(group)
    _log("reduce_scatter", tensor, axes)
    return _dispatch("reduce_scatter", axes,
                     lambda: _get_backend().reduce_scatter(tensor, op=op,
                                                           group=axes,
                                                           axis=axis))


reduce_scatter_tensor = reduce_scatter


def all_to_all_single(tensor, group=None, split_axis: int = 0,
                      concat_axis: int = 0, async_op: bool = False):
    axes = resolve_group(group if group is not None else "sp")
    _log("all_to_all_single", tensor, axes)
    return _dispatch("all_to_all_single", axes,
                     lambda: _get_backend().all_to_all(
                         tensor, group=axes, split_axis=split_axis,
                         concat_axis=concat_axis))


def broadcast(tensor, src: int = 0, group=None, async_op: bool = False):
    axes = resolve_group(group)
    _log("broadcast", tensor, axes)
    return _dispatch("broadcast", axes,
                     lambda: _get_backend().broadcast(tensor, src=src,
                                                      group=axes))


def ppermute(tensor, perm: Sequence[Tuple[int, int]], group="pp"):
    """Point-to-point stage transfer (reference pipe/p2p.py send/recv): on TPU
    the idiomatic form is a collective-permute over the pipe axis."""
    axes = resolve_group(group)
    _log("ppermute", tensor, axes)
    return _dispatch("ppermute", axes,
                     lambda: _get_backend().permute(tensor, perm, group=axes))


def axis_index(group=None):
    axes = resolve_group(group)
    return _dispatch("axis_index", axes,
                     lambda: _get_backend().axis_index(axes))


def axis_size(group=None) -> int:
    axes = resolve_group(group)
    return _dispatch("axis_size", axes,
                     lambda: _get_backend().axis_size(axes))


# --------------------------------------------------------------------- #
# comms logger config (reference comms config + log_summary comm/comm.py:422)
# --------------------------------------------------------------------- #
def configure(deepspeed_config=None, enabled=None, prof_all=None, prof_ops=None,
              verbose=None, debug=None):
    cfg = getattr(deepspeed_config, "comms_config", None)
    lg = get_comms_logger()
    if cfg is not None:
        lg.configure(enabled=cfg.enabled, verbose=cfg.verbose,
                     prof_all=cfg.prof_all, prof_ops=cfg.prof_ops,
                     debug=cfg.debug)
    lg.configure(enabled=enabled, verbose=verbose, prof_all=prof_all,
                 prof_ops=prof_ops, debug=debug)


def log_summary(show_straggler: bool = False):
    return get_comms_logger().log_all()
