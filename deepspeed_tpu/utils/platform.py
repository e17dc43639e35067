"""The one place the library asks JAX which platform it runs on.

Kernel routing (Pallas vs the XLA composition, compiled vs interpreter)
keys off this.  There is deliberately no ``try/except``: a backend that
fails to start is an error the caller must see, not "not a TPU" — a
swallowed failure would let a CPU or interpreter run pass for a chip run.
"""

from __future__ import annotations


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  Initialises the backend
    on first call; backend errors propagate."""
    import jax

    return jax.devices()[0].platform == "tpu"


def kernel_names(kernel, op_name: bool = True) -> dict:
    """Keyword arguments that name a ``pl.pallas_call`` on the device:
    ``metadata={"kernel": <the kernel function's name>}`` reaches the
    compiled custom call as ``frontend_attributes={kernel_metadata=...}``,
    which is part of the text a device trace prints for the call.  With
    ``op_name`` also ``name=``, which puts the name into the call's
    ``op_name`` and makes it the HLO instruction's name; a caller whose
    instruction name others match (the paged-attention wrappers) leaves
    that off."""
    while hasattr(kernel, "func"):          # functools.partial
        kernel = kernel.func
    names = {"metadata": {"kernel": kernel.__name__}}
    if op_name:
        names["name"] = kernel.__name__
    return names


def require_tpu(who: str):
    """For entry-point scripts that only mean something on the chip:
    ``jax.devices()``, or exit non-zero naming the platform found — a
    number from XLA's CPU backend or the Pallas interpreter is never
    printed under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"{who}: no TPU — JAX found platform "
            f"'{devices[0].platform}'; nothing runs without the chip")
    return devices


def tpu_host() -> bool:
    """True when this machine has TPU device nodes — found WITHOUT
    initialising a JAX backend, for launchers: a parent that touched JAX
    would itself hold the chips its children need."""
    import glob

    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def refuse_chip_children(n_children: int, env, what: str) -> None:
    """A chip belongs to one process at a time, and nothing here gives
    each of several local children a chip of its own: on a TPU host they
    would fail or hang on a held chip.  Raise instead, unless the
    children are pinned to the CPU (``JAX_PLATFORMS=cpu`` in ``env``)."""
    if n_children > 1 and tpu_host() and \
            env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise RuntimeError(
            f"{what}: {n_children} local processes on a TPU host. One "
            f"process drives all local chips (a jax.sharding.Mesh over "
            f"jax.devices(), or in-process replicas each on its own "
            f"device); several processes sharing the host's chips are not "
            f"supported — they would fail or hang waiting for a chip "
            f"another process holds. Set JAX_PLATFORMS=cpu for CPU-only "
            f"children.")
