"""Single-device twin of the engine's offloaded optimizer-step paths.

``DeepSpeedEngine`` needs the multi-axis mesh APIs (jax >= 0.5 on the
CPU hosts this repo's tier-1 suite documents), so the pipelined-offload
machinery would be unexercisable on those hosts.  :class:`MiniOffloadEngine`
closes that gap without forking the logic: it *borrows the engine's own
unbound methods* — ``_make_apply_step``/``_build_apply`` (the synchronous
arm), ``_build_pipelined_apply``/``_pipelined_offload_step`` (the
pipelined arm), ``_offload_transfer`` and ``_loss_scale_next`` — over a
plain one-device ``Mesh``.  A bit-exactness or TraceGuard result on the
twin is therefore a result about the engine code itself, not about a
re-implementation.

Host tier emulation, best fidelity first:

1. ``pinned_host`` memory-kind shardings when the default device
   advertises that memory space (TPU; the engine's real tier);
2. a second CPU device when ``--xla_force_host_platform_device_count>=2``
   is set (real async inter-device copies);
3. same-device shardings otherwise (placement no-ops: bit-exactness and
   trace-cleanliness remain meaningful, transfer timings do not).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.ops.optimizers import get_optimizer
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime.zero.offload import (HOST_MEMORY_KIND,
                                                OffloadPlan,
                                                OffloadTransferStats)

# a 125M-flavoured leaf mix scaled down: a few large matrices dominating
# bytes (embedding/MLP-shaped) plus many small ones (norms/biases), so
# byte-balanced bucketing has real work to do
DEFAULT_SIZES: Tuple[Tuple[int, ...], ...] = tuple(
    [(2048, 768)] * 2 + [(512, 768)] * 12 + [(768, 768)] * 2
    + [(768,)] * 8)


def pick_host_tier(device=None) -> Tuple[str, Optional[object]]:
    """(tier_name, host_device_or_None) for the twin's host emulation."""
    device = device or jax.devices()[0]
    try:
        kinds = {m.kind for m in device.addressable_memories()}
    except Exception:  # noqa: BLE001 — older backends
        kinds = set()
    if HOST_MEMORY_KIND in kinds:
        return "pinned_host", None
    same_platform = [d for d in jax.devices()
                     if d.platform == device.platform and d != device]
    if same_platform:
        return "second_device", same_platform[0]
    return "same_device", None


class TwinOffloadPlan(OffloadPlan):
    """OffloadPlan whose host tier can be a second device instead of a
    memory kind (the CPU-host emulation above); ``host_sharding=None``
    keeps the parent's memory-kind behaviour."""

    def __init__(self, shapes, ratio: float = 1.0, host_sharding=None):
        super().__init__(shapes, ratio=ratio, device="cpu")
        self._host_sharding = host_sharding

    def host_shardings(self, device_shardings):
        if self._host_sharding is None:
            return super().host_shardings(device_shardings)
        return jax.tree.map(
            lambda s, off: self._host_sharding if off else s,
            device_shardings, self.mask)


class MiniOffloadEngine:
    """The engine's offloaded optimizer step — synchronous
    whole-tree-boundary arm or pipelined per-bucket arm — on one device,
    running the REAL engine methods (see module docstring)."""

    # the engine's own step machinery, unbound — the twin supplies the
    # handful of attributes these methods touch
    _loss_scale_next = DeepSpeedEngine._loss_scale_next
    _make_apply_step = DeepSpeedEngine._make_apply_step
    _build_apply = DeepSpeedEngine._build_apply
    _make_state = DeepSpeedEngine._make_state
    _state_shardings = DeepSpeedEngine._state_shardings
    _offload_transfer = DeepSpeedEngine._offload_transfer
    _build_pipelined_apply = DeepSpeedEngine._build_pipelined_apply
    _pipelined_offload_step = DeepSpeedEngine._pipelined_offload_step

    def __init__(self, sizes: Sequence[Tuple[int, ...]] = DEFAULT_SIZES,
                 pipeline: bool = False, buffer_count: int = 4,
                 ratio: float = 1.0, fp16: bool = False,
                 gradient_clipping: float = 1.0, lr: float = 1e-3,
                 profile_transfers: bool = False, seed: int = 0,
                 host_tier: Optional[str] = None):
        cfg = {
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "gradient_clipping": gradient_clipping,
            "optimizer": {"type": "Adam", "params": {"lr": lr}},
        }
        if fp16:
            cfg["fp16"] = {"enabled": True, "loss_scale": 0,
                           "initial_scale_power": 8,
                           "loss_scale_window": 4, "hysteresis": 2,
                           "min_loss_scale": 1}
        self.config = DeepSpeedConfig(cfg)
        self.lr = lr
        self.fp16_enabled = bool(fp16)
        self.dynamic_loss_scale = self.config.dynamic_loss_scale
        self.compute_dtype = jnp.float16 if fp16 else jnp.float32
        self._initial_scale = float(2.0 ** 8) if fp16 else 1.0
        self._onebit = False
        self.optimizer_def = get_optimizer("adam", {"lr": lr})
        self.pipeline = bool(pipeline)

        dev = jax.devices()[0]
        self.mesh = Mesh(np.array([dev]), ("data",))
        dev_sharding = NamedSharding(self.mesh, P())
        tier, host_dev = pick_host_tier(dev)
        if host_tier is not None:
            if host_tier != tier and not (host_tier == "same_device"):
                raise ValueError(
                    f"requested host tier {host_tier!r}, host provides "
                    f"{tier!r}")
            tier = host_tier
        self.host_tier = tier
        if tier == "pinned_host":
            host_sharding = None  # parent memory-kind path
        elif tier == "second_device":
            host_mesh = Mesh(np.array([host_dev]), ("data",))
            host_sharding = NamedSharding(host_mesh, P())
        else:
            host_sharding = dev_sharding

        rng = np.random.default_rng(seed)
        # dict-rooted like a real model's param tree (zero-padded names
        # keep jax.tree leaf order == declaration order)
        master = {
            f"p{i:03d}": jnp.asarray(
                rng.standard_normal(s).astype(np.float32) * 0.05)
            for i, s in enumerate(sizes)}
        leaf_shardings = {k: dev_sharding for k in master}
        self._shardings = {
            "step": dev_sharding, "opt_step": dev_sharding,
            "params": dict(leaf_shardings),
            "master": dict(leaf_shardings),
            "opt": {k: dict(leaf_shardings)
                    for k in self.optimizer_def.init(master)},
            "acc_grads": dict(leaf_shardings),
            "loss_scale": dev_sharding, "good_steps": dev_sharding,
            "hysteresis": dev_sharding,
        }
        self.state = self._make_state(master)
        self._offload_plan = TwinOffloadPlan(
            jax.eval_shape(lambda t: t, master), ratio=ratio,
            host_sharding=host_sharding)
        self._offload_buckets = int(buffer_count)
        self._offload_profile = bool(profile_transfers)
        self._offload_stats = OffloadTransferStats()
        self._jit_apply = None
        self._jit_gnorm = None
        self._jit_bucket_updates = None
        self._pipe_layout = None
        self._offload_transfer(to_host=True)

    # -------------------------------------------------------------- #
    @property
    def n_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(self.state["master"]))

    def set_acc_grads(self, leaves: Sequence) -> None:
        """Install a gradient tree for the next step (already
        loss-scale-scaled, exactly as the engine's accumulators hold
        them).  Accepts host arrays; leaf order = master order."""
        keys = sorted(self._shardings["acc_grads"])
        self.state["acc_grads"] = {
            k: jax.device_put(jnp.asarray(g, jnp.float32),
                              self._shardings["acc_grads"][k])
            for k, g in zip(keys, leaves)}

    def synthetic_grads(self, step_seed: int) -> List[np.ndarray]:
        """Deterministic per-step gradients (host-side), scaled by the
        CURRENT loss scale like the engine's accumulated grads."""
        rng = np.random.default_rng(10_000 + step_seed)
        scale = float(jax.device_get(self.state["loss_scale"]))
        return [rng.standard_normal(l.shape).astype(np.float32) * scale
                for l in jax.tree.leaves(self.state["master"])]

    def step(self, lr: Optional[float] = None):
        """One optimizer step through the selected arm.  Returns the
        global grad norm (device scalar; never synced here)."""
        lr_arr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        if self.pipeline:
            gnorm, _overflow = self._pipelined_offload_step(lr_arr)
            return gnorm
        if self._jit_apply is None:
            self._build_apply()
        self._offload_transfer(to_host=False)
        self.state, gnorm, _overflow = self._jit_apply(self.state, lr_arr)
        self._offload_transfer(to_host=True)
        return gnorm

    def sync(self):
        """Block until every dispatched transfer/update has landed."""
        jax.block_until_ready(
            (self.state["master"], self.state["opt"],
             self.state["params"]))
