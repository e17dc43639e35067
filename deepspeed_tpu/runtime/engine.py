"""DeepSpeedEngine — the training engine (reference: runtime/engine.py:175).

Keeps the reference's user surface — ``loss = engine(batch)``,
``engine.backward(loss)``, ``engine.step()``, ``save_checkpoint`` /
``load_checkpoint``, gradient-accumulation boundaries, dynamic loss scaling —
re-architected for XLA:

* ``forward`` runs ONE jitted program computing loss *and* gradients
  (``jax.value_and_grad``); the host-visible fwd/bwd/step split is kept as
  bookkeeping. Splitting fwd and bwd into separate device programs (the torch
  way) would double HBM traffic for no benefit under a compiler that already
  overlaps.
* ZeRO stages 0-3 are sharding policies (:mod:`deepspeed_tpu.runtime.zero`)
  applied as jit in/out shardings — XLA inserts the reduce-scatter /
  all-gather pattern the reference hand-codes (stage_1_and_2.py:998
  ``average_tensor``, stage3.py:1179 ``__reduce_and_partition_ipg_grads``).
* fp16 dynamic loss scaling (reference runtime/fp16/loss_scaler.py) runs
  *inside* the jitted step via ``jnp.where`` — no host sync to test overflow.
* Gradient clipping is a global-norm clip over sharded grad trees; the norm's
  cross-shard reduction is inserted by XLA.

State layout (a plain pytree, so the whole engine state is one
donate-able jit argument)::

    state = {
      "step":       i32[]   global optimizer steps taken (reference global_steps)
      "opt_step":   i32[]   successful optimizer steps (bias correction clock)
      "params":     tree    compute-precision weights (bf16/fp16/fp32)
      "master":     tree    fp32 master weights          (stage>=1: sharded)
      "opt":        tree    optimizer moments            (stage>=1: sharded)
      "acc_grads":  tree    fp32 grad accumulators       (stage>=2: sharded)
      "loss_scale": f32[]   current loss scale
      "good_steps": i32[]   consecutive non-overflow steps
    }
"""

from __future__ import annotations

import inspect
import os
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.parallel import groups, tensor_overlap
from deepspeed_tpu.parallel.topology import GROUP_ALIASES, MeshTopology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.lr_schedules import LRScheduler, get_lr_schedule_fn
from deepspeed_tpu.runtime.zero import ZeroShardings
from deepspeed_tpu.observability.tracer import (build_telemetry_from_here,
                                                setup_span)
from deepspeed_tpu.ops.optimizers import OptimizerDef, get_optimizer
from deepspeed_tpu.utils.compile_cache import key_cache_on_names
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (FORWARD_MICRO_TIMER, STEP_MICRO_TIMER,
                                       NoopTimer, SynchronizedWallClockTimer,
                                       ThroughputTimer)

BATCH_AXES = GROUP_ALIASES["dp"]  # ('dout','data','expert')


def _shapes_match(args, shapes) -> bool:
    """True when ``args`` has exactly the (shape, dtype) tree the AOT
    executable was compiled for."""
    try:
        a = jax.tree.leaves(jax.tree.map(
            lambda x: (tuple(x.shape), jnp.dtype(x.dtype).name), args))
        b = jax.tree.leaves(jax.tree.map(
            lambda x: (tuple(x.shape), jnp.dtype(x.dtype).name), shapes))
        return a == b
    except Exception:  # noqa: BLE001 — any mismatch means "retrace"
        return False


def _abstract(x) -> jax.ShapeDtypeStruct:
    """Shape/dtype of a program input, with its sharding where the array
    is committed to one (an uncommitted scalar such as ``lr`` follows the
    other arguments; pinning it to its current single device would make
    the re-lowering on a multi-device mesh inconsistent)."""
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _as_model_fns(model, loss_fn) -> Tuple[Callable, Callable]:
    """Normalise a model into (init_fn, apply_fn).

    Accepted forms: a flax.linen.Module, an object with .init/.apply, or an
    (init_fn, apply_fn) tuple. ``apply_fn(params, *batch, rng=None,
    train=True)`` must return loss, (loss, aux) or outputs (with ``loss_fn``).
    """
    try:
        import flax.linen as nn

        is_linen = isinstance(model, nn.Module)
    except Exception:
        is_linen = False

    if isinstance(model, tuple) and len(model) == 2:
        return model

    if is_linen:
        call_params = ()
        try:
            call_params = tuple(
                inspect.signature(type(model).__call__).parameters)
        except (TypeError, ValueError):
            pass
        takes_det = "deterministic" in call_params
        takes_train = "train" in call_params

        def init_fn(rng, *args):
            kwargs = {}
            if takes_det:
                kwargs["deterministic"] = True
            if takes_train:
                kwargs["train"] = False
            variables = model.init(rng, *args, **kwargs)
            return variables["params"]

        def apply_fn(params, *args, rng=None, train=True):
            kwargs = {}
            if takes_det:
                kwargs["deterministic"] = not train
            if takes_train:
                kwargs["train"] = train
            rngs = {"dropout": rng} if (rng is not None and train) else None
            return model.apply({"params": params}, *args, rngs=rngs, **kwargs)

        return init_fn, apply_fn

    if hasattr(model, "init") and hasattr(model, "apply"):
        return model.init, model.apply

    raise TypeError(
        f"model must be a flax Module, (init_fn, apply_fn) pair, or expose "
        f".init/.apply — got {type(model)}")


class DeepSpeedEngine:
    """Training engine (reference runtime/engine.py:175)."""

    @setup_span("setup/engine_init")
    def __init__(self,
                 model: Any,
                 config: Any = None,
                 config_params: Any = None,
                 model_parameters: Any = None,
                 loss_fn: Optional[Callable] = None,
                 topology: Optional[MeshTopology] = None,
                 base_param_specs: Any = None,
                 batch_spec: Any = None,
                 lr_scheduler: Any = None,
                 dont_change_device: bool = False):
        self.accelerator = get_accelerator()
        # the fused step's scopes are read by name from a profile
        key_cache_on_names()
        #: the ``observability/program*`` counters and this engine's
        #: ``time_to_first_launch_s`` (``register_observability``)
        self._build_telemetry = build_telemetry_from_here()
        cfg = config if config is not None else config_params
        self.config = (cfg if isinstance(cfg, DeepSpeedConfig)
                       else DeepSpeedConfig(cfg or {}))
        self.topology = topology if topology is not None else groups.get_topology()
        groups.set_topology(self.topology)
        self.mesh = self.topology.mesh

        # Batch trio over the data-parallel axes (reference engine dp_world_size)
        self.dp_world_size = self.topology.axis_size("dp")
        self.config.resolve_batch_size(self.dp_world_size,
                                       world_size=self.topology.world_size)

        self.loss_fn = loss_fn
        self.module = model
        self._init_fn, apply_fn = _as_model_fns(model, loss_fn)
        # what parallel/tensor_overlap.py's rule decided when the model
        # was last traced on a tensor-parallel mesh:
        # {"ring": sites, "steps": n, "fallbacks": {why: sites}}
        self.tp_overlap_sites = None

        def counted_apply(*args, **kwargs):
            with tensor_overlap.recording() as sites:
                out = apply_fn(*args, **kwargs)
            if (sites["ring"] or sites["fallbacks"]) and \
                    sites != self.tp_overlap_sites:
                self.tp_overlap_sites = sites
                log_dist(tensor_overlap.describe(sites), ranks=[0])
            return out

        self._apply_fn = counted_apply

        # precision ---------------------------------------------------------
        self.compute_dtype = self.config.precision_dtype
        self.fp16_enabled = self.config.fp16.enabled
        self.bfloat16_enabled = self.config.bf16.enabled
        self.dynamic_loss_scale = self.config.dynamic_loss_scale
        if self.fp16_enabled and self.dynamic_loss_scale:
            self._initial_scale = float(2.0 ** self.config.fp16.initial_scale_power)
        elif self.fp16_enabled:
            self._initial_scale = float(self.config.fp16.loss_scale)
        else:
            self._initial_scale = 1.0

        # zero shardings ----------------------------------------------------
        self.zero_stage = self.config.zero_optimization_stage
        zc0 = self.config.zero_config
        # ZeRO++ hpZ / MiCS: secondary partition = the inner ('data',...) zero
        # sub-group; the mesh must have been built with the data axis split
        # (groups.initialize_mesh(zero_subgroup_size=k) → dout×k replicas).
        self._hpz_size = int(zc0.zero_hpz_partition_size or 1)
        self._mics_size = int(zc0.mics_shard_size or -1)
        param_axes = master_axes = grad_axes = None
        secondary = self._mics_size if self._mics_size > 0 else \
            (self._hpz_size if self._hpz_size > 1 else 0)
        if secondary:
            inner = self.topology.axis_size("zero_secondary")
            if inner != secondary:
                # inner group = data × seq × expert, so the data-axis split
                # that realises a secondary partition of `secondary` is
                # secondary / (seq*expert).
                se = self.topology.get_dim("seq") * \
                    self.topology.get_dim("expert")
                if secondary % se != 0:
                    raise ValueError(
                        f"hpZ/MiCS secondary partition size {secondary} must "
                        f"be a multiple of seq*expert parallel degree {se} "
                        f"(the inner zero group spans ('data','seq',"
                        f"'expert'))")
                raise ValueError(
                    f"hpZ/MiCS secondary partition size {secondary} requires "
                    f"the mesh's inner zero group ('data','seq','expert') to "
                    f"have that size (got {inner}); build the mesh with "
                    f"groups.initialize_mesh(zero_subgroup_size="
                    f"{secondary // se}, ...)")
            param_axes = GROUP_ALIASES["zero_secondary"]
            if self._mics_size > 0:
                # MiCS: *all* state confined to the sub-group (zero/mics.py);
                # gradient reduction still spans all replicas (hierarchical
                # allreduce = XLA reduce-scatter(inner) + all-reduce(dout)).
                master_axes = param_axes
                grad_axes = param_axes
        self.zero = ZeroShardings(
            self.zero_stage, self.topology,
            param_persistence_threshold=zc0.param_persistence_threshold
            if self.zero_stage >= 3 else 0,
            param_axes=param_axes, master_axes=master_axes,
            grad_axes=grad_axes)

        # offload (reference zero/parameter_offload.py; OffloadPP ratio) ----
        from deepspeed_tpu.runtime.zero.offload import validate_offload_config

        zc = self.config.zero_config
        self._offload_device = validate_offload_config(
            zc.offload_optimizer, self.zero_stage, "offload_optimizer")
        self._offload_ratio = (zc.offload_optimizer.ratio
                               if self._offload_device else 0.0)
        self._offload_plan = None  # built with the shardings
        # pipelined host-Adam: split the offload boundary into per-bucket
        # H2D -> update -> D2H streams (buffer_count in-flight slots)
        oc = zc.offload_optimizer
        self._offload_pipeline = bool(
            self._offload_device and oc.pipeline_enabled)
        if self._offload_pipeline and self._offload_device != "cpu":
            raise ValueError(
                "offload_optimizer.pipeline applies to device='cpu' "
                "(the NVMe tier has its own pipelined AIO path — "
                "swap_tensor.PartitionedOptimizerSwapper)")
        if self._offload_pipeline and self.config.flops_profiler.enabled:
            # the profiler AOT-compiles the whole-tree apply program;
            # per-bucket programs have no single executable to profile
            log_dist("offload pipeline: disabled under flops_profiler "
                     "(whole-tree apply is what the profiler costs)",
                     ranks=[0])
            self._offload_pipeline = False
        self._offload_buckets = int(oc.buffer_count) if oc else 4
        self._offload_profile = bool(oc and oc.profile_transfers)
        self._offload_stats = None
        if self._offload_device:
            from deepspeed_tpu.runtime.zero.offload import (
                OffloadTransferStats)

            self._offload_stats = OffloadTransferStats()
        # pipelined-apply program cache (built at first pipelined step)
        self._jit_gnorm = None
        self._jit_bucket_updates = None
        self._pipe_layout = None
        # offload_param (the other half of ZeRO-Infinity, reference
        # zero/partition_parameters.py NVMe path): compute-precision params
        # are HOST-resident between steps; each forward stages them to HBM
        # and the step's epilogue streams them back. HBM then holds params
        # only while a program is computing.
        self._offload_param_device = validate_offload_config(
            zc.offload_param, self.zero_stage, "offload_param")
        if self._offload_param_device is not None:
            if self.zero_stage < 3:
                raise ValueError(
                    "offload_param requires ZeRO stage 3 (reference "
                    "constraint: only stage 3 partitions parameters)")
        self._param_offload_plan = None  # built with the shardings
        self._params_on_host = False
        self.base_param_specs = base_param_specs
        if self.base_param_specs is None:
            self.base_param_specs = getattr(model, "partition_rules", None)
        self._batch_spec = batch_spec

        # optimizer ---------------------------------------------------------
        opt_cfg = self.config.optimizer
        if opt_cfg is None:
            opt_cfg_type, opt_params = "adamw", {}
        else:
            opt_cfg_type, opt_params = opt_cfg.type, dict(opt_cfg.params)
        self._base_lr = float(opt_params.get("lr", 1e-3))
        from deepspeed_tpu.runtime.fp16 import onebit as onebit_mod  # registers

        self.optimizer_def: OptimizerDef = get_optimizer(opt_cfg_type, opt_params)
        self.optimizer = self  # reference returns engine.optimizer; state lives here
        # 1-bit optimizers own gradient communication (reference
        # runtime/fp16/onebit/): per-device grad accumulation + compressed
        # momentum allreduce after freeze_step.
        self._onebit = self.optimizer_def.name in onebit_mod.ONEBIT_NAMES
        self._jit_apply_compressed = None
        self._onebit_update_var = None
        if self._onebit:
            self._onebit_world = onebit_mod.validate_onebit_mesh(self)

        # lr scheduler ------------------------------------------------------
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        elif self.config.scheduler is not None and self.config.scheduler.type:
            fn = get_lr_schedule_fn(self.config.scheduler.type,
                                    {**self.config.scheduler.params,
                                     "lr": self._base_lr})
            self.lr_scheduler = LRScheduler(fn)
        else:
            self.lr_scheduler = None

        # bookkeeping -------------------------------------------------------
        self.state: Optional[Dict[str, Any]] = None
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        # fp16 skipped-step tally: a host int base plus an ON-DEVICE
        # overflow accumulator, so the hot path never blocks to read the
        # flag (the `skipped_steps` property fetches lazily)
        self._skipped_steps_base = 0
        self._overflow_accum = None
        self._skipped_steps_logged = 0
        self._last_loss = None
        self._seen_backward = False
        self.training = True
        self.gradient_accumulation_steps = lambda: \
            self.config.gradient_accumulation_steps
        self.train_micro_batch_size_per_gpu = lambda: \
            self.config.train_micro_batch_size_per_gpu
        self.train_batch_size = lambda: self.config.train_batch_size

        # jit cache ---------------------------------------------------------
        self._jit_micro: Optional[Callable] = None
        self._jit_apply: Optional[Callable] = None
        self._jit_eval: Optional[Callable] = None
        self._jit_fused: Optional[Callable] = None
        self._jit_train_batch: Optional[Callable] = None
        self._pending_step = None  # (gnorm, overflow) from a fused forward
        self._accum_pending = False  # grads accumulated but not yet stepped
        self._micro_compiled = None  # AOT executables (flops profiler path)
        self._apply_compiled = None
        self._apply_in_shapes = None
        self._fused_in_shapes = None  # fused-step shapes (memory ledger)
        self._shardings: Optional[Dict[str, Any]] = None
        self._param_use_shardings = None  # stage 3: params as gathered
        self._rng = jax.random.key(self.config.seed)

        from deepspeed_tpu.monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(self.config)

        # data efficiency: curriculum, random-LTD, progressive layer drop
        # (reference runtime/data_pipeline/, progressive_layer_drop.py)
        self.curriculum_scheduler = None
        self.random_ltd_scheduler = None
        self.progressive_layer_drop = None
        cl_cfg = self.config.curriculum_learning or {}
        de = self.config.data_efficiency or {}
        if not cl_cfg.get("enabled", False):
            cl_cfg = de.get("data_sampling", {}).get("curriculum_learning",
                                                     {})
            # reference data-efficiency format nests the schedule under
            # curriculum_metrics.<metric_name>
            metrics = cl_cfg.get("curriculum_metrics")
            if cl_cfg.get("enabled", False) and metrics:
                name, mcfg = next(iter(metrics.items()))
                cl_cfg = {"enabled": True, "curriculum_type": name, **mcfg}
        if cl_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.data_pipeline import (
                CurriculumScheduler)

            self.curriculum_scheduler = CurriculumScheduler(cl_cfg)
        ltd_cfg = de.get("data_routing", {}).get("random_ltd", {})
        if ltd_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.data_pipeline import RandomLTDScheduler

            self.random_ltd_scheduler = RandomLTDScheduler(ltd_cfg)
        pld_cfg = self.config.progressive_layer_drop or {}
        if pld_cfg.get("enabled", False):
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                ProgressiveLayerDrop)

            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld_cfg.get("theta", 0.5),
                gamma=pld_cfg.get("gamma", 0.001))
        if "activation_checkpointing" in self.config._param_dict:
            from deepspeed_tpu.runtime.activation_checkpointing import (
                checkpointing)

            checkpointing.configure(deepspeed_config=self.config)

        # timers / throughput / flops profiler (reference utils/timer.py:43,
        # runtime/engine.py:140 EngineTimers, profiling/flops_profiler) -----
        self.wall_clock_breakdown = lambda: self.config.wall_clock_breakdown
        self.timers = (SynchronizedWallClockTimer()
                       if self.config.wall_clock_breakdown else NoopTimer())
        self.tput_timer = ThroughputTimer(
            batch_size=self.config.train_batch_size,
            steps_per_output=self.config.steps_per_print)
        self.flops_profiler = None
        self._micro_in_shapes = None  # ShapeDtypeStructs for AOT cost analysis

        import deepspeed_tpu.comm as dist

        dist.configure(self.config)

        log_dist(
            f"DeepSpeedEngine: zero_stage={self.zero_stage} "
            f"dtype={self.compute_dtype.__name__ if hasattr(self.compute_dtype, '__name__') else self.compute_dtype} "
            f"mesh={self.topology.dims.as_dict()} "
            f"micro_batch={self.config.train_micro_batch_size_per_gpu} "
            f"gas={self.config.gradient_accumulation_steps}", ranks=[0])

        if model_parameters is not None:
            self.init_state_from_params(model_parameters)

    # ------------------------------------------------------------------ #
    # Sharding / state construction
    # ------------------------------------------------------------------ #
    def _resolve_base_specs(self, params_shapes):
        """TP base specs: None, a spec tree, or list of (regex, PartitionSpec)
        rules matched against '/'-joined param paths."""
        rules = self.base_param_specs
        if rules is None:
            return jax.tree.map(lambda _: None, params_shapes)
        if isinstance(rules, (list, tuple)) and rules and isinstance(rules[0], tuple):
            import re

            flat = jax.tree_util.tree_flatten_with_path(params_shapes)[0]

            def match(path):
                name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                                for k in path)
                for pat, spec in rules:
                    if re.search(pat, name):
                        return spec
                return None

            paths = {tuple(p): match(p) for p, _ in flat}
            return jax.tree_util.tree_map_with_path(
                lambda p, _: paths.get(tuple(p)), params_shapes)
        return rules  # assume spec tree

    def _build_shardings(self, params_shapes):
        base = self._resolve_base_specs(params_shapes)
        mesh = self.mesh
        named = lambda tree: jax.tree.map(
            lambda s: NamedSharding(mesh, s if s is not None else P()), tree,
            is_leaf=lambda x: x is None or isinstance(x, P))
        param_s = named(self.zero.param_specs(params_shapes, base))
        master_s = named(self.zero.master_specs(params_shapes, base))
        grad_s = named(self.zero.grad_specs(params_shapes, base))
        # stage 3 only: what each leaf is gathered to where it is used
        self._param_use_shardings = named(
            self.zero.param_use_specs(params_shapes, base)) \
            if self.zero_stage >= 3 else None
        scalar = NamedSharding(mesh, P())
        opt_shapes = jax.eval_shape(self.optimizer_def.init, params_shapes)
        # moments mirror the master sharding of their parameter
        opt_s = {k: jax.tree.map(lambda _m, s: s, opt_shapes[k], master_s)
                 for k in opt_shapes}
        self._shardings = {
            "step": scalar, "opt_step": scalar,
            "params": param_s, "master": master_s, "opt": opt_s,
            "acc_grads": grad_s,
            "loss_scale": scalar, "good_steps": scalar, "hysteresis": scalar,
        }
        if self._onebit:
            # per-device grad accumulator [W, ...] + comm error feedback
            # state, all sharded over the dp axes on dim 0
            dev_sharded = NamedSharding(mesh, P(BATCH_AXES))
            self._shardings["acc_grads"] = jax.tree.map(
                lambda _s: dev_sharded, grad_s)
            self._shardings["comm_error_worker"] = jax.tree.map(
                lambda _s: dev_sharded, grad_s)
            self._shardings["comm_error_server"] = jax.tree.map(
                lambda _s: dev_sharded, grad_s)
        if self._offload_device:
            from deepspeed_tpu.runtime.zero.offload import OffloadPlan

            self._offload_plan = OffloadPlan(
                params_shapes, ratio=self._offload_ratio,
                device=self._offload_device,
                nvme_path=self.config.zero_config.offload_optimizer.nvme_path)
            log_dist(
                f"ZeRO-Offload: optimizer state -> "
                f"{self._offload_device} "
                f"({self._offload_plan.fraction:.0%} of elements, "
                f"ratio={self._offload_ratio})", ranks=[0])
        if self._offload_param_device:
            from deepspeed_tpu.runtime.zero.offload import OffloadPlan

            self._param_offload_plan = OffloadPlan(
                params_shapes, ratio=1.0,
                device=self._offload_param_device,
                nvme_path=self.config.zero_config.offload_param.nvme_path
                if self._offload_param_device == "nvme" else None)
            log_dist(
                "ZeRO-Infinity: compute params "
                + ("on NVMe swap files (pipelined AIO prefetch)"
                   if self._offload_param_device == "nvme"
                   else "host-resident")
                + " between steps (offload_param.device="
                f"{self._offload_param_device})", ranks=[0])
        return self._shardings

    def _state_shardings(self):
        assert self._shardings is not None, "engine state not initialised"
        return self._shardings

    def init_state_from_params(self, host_params) -> None:
        """Place an existing host/device param tree into sharded engine state."""
        shapes = jax.eval_shape(lambda p: p, host_params)
        sh = self._build_shardings(shapes)
        self.state = jax.jit(
            lambda p: self._make_state(
                jax.tree.map(lambda x: x.astype(jnp.float32), p)),
            out_shardings=dict(sh))(host_params)
        if self._offload_plan is not None:
            self._offload_transfer(to_host=True)
        self._param_offload_transfer(to_host=True)

    @setup_span("setup/init_parameters")
    def initialize_parameters(self, *sample_args, seed: Optional[int] = None):
        """Construct params directly sharded (the reference's ``zero.Init``
        construction-time partitioning, partition_parameters.py:734 — here a
        jitted init with sharded out_shardings, so no rank ever materialises
        the full model)."""
        rng = jax.random.key(seed if seed is not None else self.config.seed)
        shapes = jax.eval_shape(self._init_fn, rng, *sample_args)
        sh = self._build_shardings(shapes)

        def build(rng, *args):
            params32 = self._init_fn(rng, *args)
            params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params32)
            return self._make_state(params32)

        self.state = jax.jit(build, out_shardings=dict(sh))(rng, *sample_args)
        if self._offload_plan is not None:
            self._offload_transfer(to_host=True)
        self._param_offload_transfer(to_host=True)
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        log_dist(f"initialized {n_params/1e6:.2f}M parameters", ranks=[0])
        return self.state

    def _make_state(self, params32):
        if self._onebit:
            w = self._onebit_world
            zeros = jax.tree.map(
                lambda p: jnp.zeros((w,) + p.shape, jnp.float32), params32)
        else:
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params32)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "opt_step": jnp.zeros((), jnp.int32),
            "params": jax.tree.map(lambda p: p.astype(self.compute_dtype), params32),
            "master": params32,
            "opt": self.optimizer_def.init(params32),
            "acc_grads": zeros,
            "loss_scale": jnp.asarray(self._initial_scale, jnp.float32),
            "good_steps": jnp.zeros((), jnp.int32),
            "hysteresis": jnp.asarray(self.config.fp16.hysteresis, jnp.int32),
        }
        if self._onebit:
            from deepspeed_tpu.runtime.fp16.onebit import make_error_state

            werr, serr = make_error_state(params32, self._onebit_world)
            state["comm_error_worker"] = werr
            state["comm_error_server"] = serr
        return state

    # ------------------------------------------------------------------ #
    # Batch placement
    # ------------------------------------------------------------------ #
    def batch_sharding(self, leaf) -> NamedSharding:
        if self._batch_spec is not None:
            spec = self._batch_spec(leaf) if callable(self._batch_spec) \
                else self._batch_spec
        else:
            spec = P(BATCH_AXES) if getattr(leaf, "ndim", 0) >= 1 else P()
        return NamedSharding(self.mesh, spec)

    def shard_batch(self, batch):
        """Place a host (global) micro-batch onto the mesh, sharded over the
        data-parallel axes."""
        return jax.tree.map(
            lambda leaf: jax.device_put(leaf, self.batch_sharding(leaf)), batch)

    # ------------------------------------------------------------------ #
    # Jitted programs
    # ------------------------------------------------------------------ #
    def _loss_from_outputs(self, out, args):
        if self.loss_fn is not None:
            return self.loss_fn(out, *args), None
        if isinstance(out, tuple):
            return out[0], out[1:]
        return out, None

    def _grad_accum_divisor(self) -> float:
        """Loss divisor per micro program (PipelineEngine overrides: its one
        program already averages over all microbatches)."""
        return float(self.config.gradient_accumulation_steps)

    def _make_micro_grads(self):
        """One micro-batch's scaled loss + raw gradients (compute dtype —
        no fp32 materialisation)."""
        gas = self._grad_accum_divisor()

        def micro_grads(params, scale, rng, args):
            if self.zero_stage >= 3:
                with jax.named_scope("zero/gather"):
                    # Gather on use: each leaf is constrained to the spec it
                    # has while it is used (its TP base spec), so the model
                    # multiplies by a weight that is whole over the ZeRO
                    # axes and activations keep the Megatron placement
                    # (batch over 'data', heads / intermediate over
                    # 'model').  The stored spec alone does not say that:
                    # on a data x model mesh GSPMD reads P('data','model')
                    # as 2-D tensor parallelism and reshards the
                    # ACTIVATIONS to the weights (zero/partition.py).  The
                    # constraint sits outside the differentiated function,
                    # so it binds the primal only: no cotangent is pinned
                    # to the gathered spec, and each weight gradient is
                    # reduce-scattered straight to its grad spec.  XLA keeps
                    # a gathered weight for the backward pass while memory
                    # allows and gathers it again when it does not.
                    params = jax.lax.with_sharding_constraint(
                        params, self._param_use_shardings)

            def scaled_loss_fn(p):
                out = self._apply_fn(p, *args, rng=rng, train=True)
                loss, _aux = self._loss_from_outputs(out, args)
                return loss.astype(jnp.float32) * (scale / gas), loss

            (_, loss), grads = jax.value_and_grad(
                scaled_loss_fn, has_aux=True)(params)
            return grads, loss

        return micro_grads

    def _make_micro_accumulate(self):
        """Shared closure: one micro-batch's scaled loss + gradient
        accumulation (used by the micro program and train_batch's scan
        body; the fused gas=1 step skips the accumulator entirely)."""
        micro_grads = self._make_micro_grads()

        def micro_acc(params, acc_grads, scale, rng, args):
            grads, loss = micro_grads(params, scale, rng, args)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                               acc_grads, grads)
            return acc, loss

        return micro_acc

    def _build_micro(self):
        """The micro program reads ONLY (params, acc_grads, loss_scale) —
        master weights and optimizer moments never flow through it, so with
        offload enabled they stay host-resident across micro-steps."""
        if self._onebit:
            from deepspeed_tpu.runtime.fp16.onebit import build_local_grad_micro

            self._jit_micro = build_local_grad_micro(self)
            return
        zc = self.config.zero_config
        if (zc.zero_quantized_weights and self.zero_stage >= 3) or \
                zc.zero_quantized_gradients:
            from deepspeed_tpu.runtime.zero.zeropp import build_quantized_micro

            log_dist(
                "ZeRO++: quantized "
                f"{'weight all-gather ' if zc.zero_quantized_weights else ''}"
                f"{'gradient reduce-scatter' if zc.zero_quantized_gradients else ''}"
                " (int8 wire format)", ranks=[0])
            self._jit_micro = build_quantized_micro(self)
            return
        sh = self._state_shardings()
        micro_acc = self._make_micro_accumulate()

        def micro(params, acc_grads, scale, rng, *args):
            return micro_acc(params, acc_grads, scale, rng, args)

        self._jit_micro = jax.jit(
            micro,
            donate_argnums=(1,),
            out_shardings=(sh["acc_grads"], NamedSharding(self.mesh, P())))

    def _loss_scale_next(self, scale, good, hyst, overflow):
        """Dynamic loss scale bookkeeping (reference fp16/loss_scaler.py
        DynamicLossScaler: only lower the scale once `hysteresis`
        consecutive overflows have drained the counter).  Pure traced
        arithmetic — shared by the whole-tree apply program and the
        pipelined step's scalar-tail program so the two paths cannot
        drift."""
        if not (self.fp16_enabled and self.dynamic_loss_scale):
            return scale, good, hyst
        cfg = self.config.fp16
        window = cfg.loss_scale_window
        lower = overflow & (hyst <= 1)
        grow = ~overflow & (good + 1 >= window)
        new_scale = jnp.where(
            lower, jnp.maximum(scale / 2.0, cfg.min_loss_scale),
            jnp.where(grow, scale * 2.0, scale))
        new_good = jnp.where(overflow | grow, 0, good + 1)
        full = jnp.asarray(cfg.hysteresis, jnp.int32)
        if cfg.consecutive_hysteresis:
            # refill on every non-overflow step
            new_hyst = jnp.where(overflow, jnp.maximum(hyst - 1, 1), full)
        else:
            # refill only when the scale window elapses cleanly
            new_hyst = jnp.where(overflow, jnp.maximum(hyst - 1, 1),
                                 jnp.where(grow, full, hyst))
        return new_scale, new_good, new_hyst

    def _make_apply_step(self):
        """The pure optimizer-step closure, shared by the standalone apply
        program and the fused micro+apply program."""
        clip = float(self.config.gradient_clipping)
        fp16 = self.fp16_enabled
        dynamic = self.dynamic_loss_scale

        onebit = self._onebit

        def apply_step(state, lr, grads=None):
            # ``grads`` given (fused gas=1 path): feed the raw compute-dtype
            # grads straight into the update and leave the (donated, all
            # zero) acc_grads untouched — skipping the fp32 accumulator
            # round-trip (~1.6 GB/step of HBM traffic on the 125M bench).
            direct_grads = grads is not None
            if grads is None:
                grads = state["acc_grads"]
            if fp16 or dynamic:
                inv_scale = 1.0 / state["loss_scale"]
                grads = jax.tree.map(lambda g: g * inv_scale, grads)
            if onebit:
                # warmup phase: average the per-device accumulators in full
                # precision (XLA reduces the dp-sharded leading dim)
                grads = jax.tree.map(lambda g: g.mean(axis=0), grads)
            # device scopes: optimizer/clip (the global norm and the
            # scaling), optimizer/<name> (the update, the overflow guard
            # and the cast back to the compute dtype)
            with jax.named_scope("optimizer/clip"):
                # global grad norm (sharded leaves -> XLA inserts the
                # reduction; fp32 accumulation regardless of grad dtype)
                sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in jax.tree.leaves(grads))
                gnorm = jnp.sqrt(sumsq)
                overflow = ~jnp.isfinite(gnorm) if fp16 \
                    else jnp.asarray(False)
                if clip > 0.0:
                    coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    # f32 coef promotes bf16 grads to f32 inside the
                    # (fused) update kernel — no extra materialised tree
                    grads = jax.tree.map(lambda g: g * coef, grads)

            opt_step_next = state["opt_step"] + 1
            with jax.named_scope(f"optimizer/{self.optimizer_def.name}"):
                new_master, new_opt = self.optimizer_def.update(
                    grads, state["opt"], state["master"], lr, opt_step_next)

                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(overflow, o, n), new, old)
                new_master = keep(new_master, state["master"])
                new_opt = keep(new_opt, state["opt"])
                new_params = jax.tree.map(
                    lambda m: m.astype(self.compute_dtype), new_master)

            new_scale, new_good, new_hyst = self._loss_scale_next(
                state["loss_scale"], state["good_steps"],
                state["hysteresis"], overflow)

            new_state = dict(state)  # passthrough for extra keys (1-bit
            # comm errors stay zero through warmup)
            new_state.update({
                "step": state["step"] + 1,
                "opt_step": jnp.where(overflow, state["opt_step"], opt_step_next),
                "params": new_params,
                "master": new_master,
                "opt": new_opt,
                # direct-grad path: acc_grads were never touched (still
                # zero) — pass the donated buffers through unchanged
                "acc_grads": state["acc_grads"] if direct_grads else
                jax.tree.map(jnp.zeros_like, state["acc_grads"]),
                "loss_scale": new_scale,
                "good_steps": new_good,
                "hysteresis": new_hyst,
            })
            return new_state, gnorm, overflow

        return apply_step

    def _build_apply(self):
        sh = self._state_shardings()
        scalar = NamedSharding(self.mesh, P())
        self._jit_apply = jax.jit(
            self._make_apply_step(),
            donate_argnums=(0,),
            out_shardings=(dict(sh), scalar, scalar))

    # ------------------------------------------------------------------ #
    # Pipelined host-Adam (offload_optimizer.pipeline): the synchronous
    # whole-tree placement boundary (OffloadPlan.place on both sides of
    # the apply program) becomes per-bucket streams — while bucket k's
    # updated master/opt leaves stream back to pinned_host, bucket k+1
    # runs its update on the device, and the final spill overlaps the
    # next step's forward (nothing below ever blocks the host).  The
    # update math is the synchronous apply program split leaf-wise:
    # identical per-leaf expressions fed by one shared gnorm program, so
    # the two paths are bit-exact.
    # ------------------------------------------------------------------ #
    def _build_pipelined_apply(self):
        """Compile the pipelined step's programs: one global-gnorm
        program, one donated per-bucket update program per transfer
        bucket (double-buffered slots: bucket k's donated inputs free
        while bucket k+1's H2D copies arrive), and one scalar-tail
        program for the step/scale bookkeeping.  All shapes are fixed at
        build time — steady state retraces nothing."""
        plan = self._offload_plan
        sh = self._state_shardings()
        scalar = NamedSharding(self.mesh, P())
        fp16, dynamic = self.fp16_enabled, self.dynamic_loss_scale
        clip = float(self.config.gradient_clipping)

        def head_fn(acc_grads, loss_scale, step, opt_step, good, hyst):
            # one dispatch for the whole scalar plane: global grad norm,
            # overflow, and the next step/opt_step/loss-scale scalars —
            # everything the bucket programs and the state rebuild need,
            # computed up front so the scalars land while buckets stream
            grads = acc_grads
            if fp16 or dynamic:
                inv_scale = 1.0 / loss_scale
                grads = jax.tree.map(lambda g: g * inv_scale, grads)
            sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads))
            gnorm = jnp.sqrt(sumsq)
            overflow = ~jnp.isfinite(gnorm) if fp16 else jnp.asarray(False)
            opt_step_next = opt_step + 1
            new_scale, new_good, new_hyst = self._loss_scale_next(
                loss_scale, good, hyst, overflow)
            return (gnorm, overflow, step + 1,
                    jnp.where(overflow, opt_step, opt_step_next),
                    new_scale, new_good, new_hyst)

        self._jit_gnorm = jax.jit(head_fn, out_shardings=(scalar,) * 7)

        def bucket_update(master, opt, acc, params, lr, opt_step,
                          loss_scale, gnorm, overflow):
            # master/acc/params: leaf LISTS (not tuples — the optimizer
            # defs unpack per-leaf results with is_leaf=isinstance(
            # tuple), so a tuple-rooted tree would read as one leaf);
            # opt: {moment: leaf list}.  ``params`` is donation fodder
            # only — its values are never read, but without it the cast
            # output would be a fresh allocation every step (the
            # synchronous apply reuses the donated state's params
            # buffers; the bucket program must too).  The synchronous
            # apply's per-leaf math verbatim, on a slice
            del params
            grads = acc
            if fp16 or dynamic:
                inv_scale = 1.0 / loss_scale
                grads = jax.tree.map(lambda g: g * inv_scale, grads)
            if clip > 0.0:
                coef = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)
            opt_step_next = opt_step + 1
            new_master, new_opt = self.optimizer_def.update(
                grads, opt, master, lr, opt_step_next)
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_master = keep(new_master, master)
            new_opt = keep(new_opt, opt)
            new_params = jax.tree.map(
                lambda m: m.astype(self.compute_dtype), new_master)
            return (new_params, new_master, new_opt,
                    jax.tree.map(jnp.zeros_like, acc))

        # flat layout (treedef order shared by master/opt/grads/params)
        m_sh, m_def = jax.tree_util.tree_flatten(sh["master"])
        p_sh = jax.tree_util.tree_flatten(sh["params"])[0]
        g_sh = jax.tree_util.tree_flatten(sh["acc_grads"])[0]
        opt_keys = sorted(sh["opt"])
        o_sh = {k: jax.tree_util.tree_flatten(sh["opt"][k])[0]
                for k in opt_keys}
        for k in opt_keys:
            if len(o_sh[k]) != len(m_sh):
                raise ValueError(
                    f"offload pipeline: optimizer moment tree {k!r} is "
                    f"not leaf-parallel to the master tree "
                    f"({len(o_sh[k])} vs {len(m_sh)} leaves)")
        m_host = jax.tree_util.tree_flatten(
            plan.host_shardings(sh["master"]))[0]
        o_host = {k: jax.tree_util.tree_flatten(
            plan.host_shardings(sh["opt"][k]))[0] for k in opt_keys}
        transfer, resident = plan.pipeline_buckets(self._offload_buckets)
        buckets = [(idx, True) for idx in transfer]
        if resident:
            # twin-flow device-resident leaves: same update program, no
            # transfers — scheduled first so their compute overlaps the
            # first offloaded bucket's H2D stream
            buckets.insert(0, (resident, False))
        # master f32 + one f32 moment per optimizer slot
        leaf_bytes = [4 * s * (1 + len(opt_keys))
                      for s in plan.flat_sizes]
        self._jit_bucket_updates = [
            jax.jit(bucket_update, donate_argnums=(0, 1, 2, 3),
                    out_shardings=(
                        [p_sh[i] for i in idx],
                        [m_sh[i] for i in idx],
                        {k: [o_sh[k][i] for i in idx]
                         for k in opt_keys},
                        [g_sh[i] for i in idx]))
            for idx, _t in buckets]
        self._pipe_layout = {
            "m_def": m_def, "opt_keys": opt_keys, "buckets": buckets,
            "m_sh": m_sh, "o_sh": o_sh, "m_host": m_host,
            "o_host": o_host, "leaf_bytes": leaf_bytes,
        }

    def _pipelined_offload_step(self, lr):
        """One optimizer step through the per-bucket offload streams.
        Pure async dispatch — no ``device_get``/``block_until_ready`` in
        steady state (TraceGuard-enforced in tests); the only blocking
        form lives behind ``offload_optimizer.profile_transfers``."""
        if self._pipe_layout is None:
            self._build_pipelined_apply()
        lay, state = self._pipe_layout, self.state
        stats = self._offload_stats
        opt_keys = lay["opt_keys"]
        m_flat = jax.tree_util.tree_flatten(state["master"])[0]
        p_flat = jax.tree_util.tree_flatten(state["params"])[0]
        g_flat = jax.tree_util.tree_flatten(state["acc_grads"])[0]
        o_flat = {k: jax.tree_util.tree_flatten(state["opt"][k])[0]
                  for k in opt_keys}
        (gnorm, overflow, new_step, new_opt_step, new_scale, new_good,
         new_hyst) = self._jit_gnorm(
            state["acc_grads"], state["loss_scale"], state["step"],
            state["opt_step"], state["good_steps"], state["hysteresis"])

        def restore(idx, overlapped):
            # H2D: ONE batched dispatch for the whole bucket (per-leaf
            # device_put in a transfer loop is the serial-dispatch bug
            # class the batched KV spool fix killed); the copies land
            # while an earlier bucket's update computes
            srcs = [m_flat[i] for i in idx]
            dsts = [lay["m_sh"][i] for i in idx]
            for k in opt_keys:
                srcs.extend(o_flat[k][i] for i in idx)
                dsts.extend(lay["o_sh"][k][i] for i in idx)
            moved = jax.device_put(srcs, dsts)
            for j, i in enumerate(idx):
                m_flat[i] = moved[j]
                stats.note_restore(lay["leaf_bytes"][i], overlapped)
            for kk, k in enumerate(opt_keys):
                base = (kk + 1) * len(idx)
                for j, i in enumerate(idx):
                    o_flat[k][i] = moved[base + j]
            if self._offload_profile and moved:
                stats.timed_wait(moved)

        buckets = lay["buckets"]
        first_transfer = next(
            (bi for bi, (_idx, t) in enumerate(buckets) if t), None)
        if first_transfer is not None:
            restore(buckets[first_transfer][0], overlapped=False)
        for bi, (idx, is_transfer) in enumerate(buckets):
            nxt = bi + 1
            if nxt < len(buckets) and buckets[nxt][1] \
                    and nxt != first_transfer:
                # prefetch bucket k+1 while bucket k's update runs
                restore(buckets[nxt][0], overlapped=True)
            new_p, new_m, new_o, new_g = self._jit_bucket_updates[bi](
                [m_flat[i] for i in idx],
                {k: [o_flat[k][i] for i in idx] for k in opt_keys},
                [g_flat[i] for i in idx],
                [p_flat[i] for i in idx],
                lr, state["opt_step"], state["loss_scale"], gnorm,
                overflow)
            for j, i in enumerate(idx):
                p_flat[i] = new_p[j]
                g_flat[i] = new_g[j]
            if is_transfer:
                # D2H: one batched dispatch — the spill overlaps bucket
                # k+1's update, and the last bucket's spill overlaps the
                # NEXT step's forward (params don't depend on master/opt)
                srcs = list(new_m)
                dsts = [lay["m_host"][i] for i in idx]
                for k in opt_keys:
                    srcs.extend(new_o[k])
                    dsts.extend(lay["o_host"][k][i] for i in idx)
                spilled = jax.device_put(srcs, dsts)
                for j, i in enumerate(idx):
                    m_flat[i] = spilled[j]
                    stats.note_spill(lay["leaf_bytes"][i],
                                     overlapped=True)
                for kk, k in enumerate(opt_keys):
                    base = (kk + 1) * len(idx)
                    for j, i in enumerate(idx):
                        o_flat[k][i] = spilled[base + j]
                if self._offload_profile:
                    stats.timed_wait(spilled)
            else:
                for j, i in enumerate(idx):
                    m_flat[i] = new_m[j]
                    for k in opt_keys:
                        o_flat[k][i] = new_o[k][j]
        stats.note_step(sum(1 for _idx, t in buckets if t))
        unflat = lambda flat: jax.tree_util.tree_unflatten(
            lay["m_def"], flat)
        self.state = dict(
            state, step=new_step, opt_step=new_opt_step,
            params=unflat(p_flat), master=unflat(m_flat),
            opt={k: unflat(o_flat[k]) for k in opt_keys},
            acc_grads=unflat(g_flat), loss_scale=new_scale,
            good_steps=new_good, hysteresis=new_hyst)
        return gnorm, overflow

    def _can_fuse_step(self) -> bool:
        """One combined micro+apply program per optimizer step — valid when
        every micro step IS a boundary (gas=1) and no phase/placement
        machinery needs a host hop between gradient and update (offload
        transfers, 1-bit phase switch, ZeRO++ manual micro, flops-profiler
        AOT bookkeeping). Halves the per-step dispatch count and lets XLA
        overlap the optimizer with the backward tail."""
        zc = self.config.zero_config
        return (self.config.fuse_optimizer_step
                and self.config.gradient_accumulation_steps == 1
                and not self._onebit
                and self._offload_plan is None and not self._offload_device
                and not self._offload_param_device
                and not zc.zero_quantized_gradients
                and not (zc.zero_quantized_weights and self.zero_stage >= 3)
                and not self.config.flops_profiler.enabled
                # wall_clock_breakdown asks for separate fwd/step timings,
                # which a single fused program cannot attribute
                and not self.config.wall_clock_breakdown)

    def _build_fused_step(self):
        """micro (loss+grads) and optimizer apply in ONE jitted program.
        Grads flow straight from autodiff into the update — the fp32
        accumulator is bypassed (it exists for gas>1)."""
        sh = self._state_shardings()
        apply_step = self._make_apply_step()
        micro_grads = self._make_micro_grads()

        def fused(state, lr, rng, *args):
            grads, loss = micro_grads(state["params"], state["loss_scale"],
                                      rng, args)
            new_state, gnorm, overflow = apply_step(state, lr, grads=grads)
            return new_state, loss, gnorm, overflow

        scalar = NamedSharding(self.mesh, P())
        self._jit_fused = jax.jit(
            fused,
            donate_argnums=(0,),
            out_shardings=(dict(sh), scalar, scalar, scalar))

    def _build_train_batch(self):
        """One jitted program for a FULL training batch: ``lax.scan`` over
        the gradient-accumulation micro-batches, then the optimizer apply
        (reference ``train_batch`` semantics, pipe/engine.py:321, here for
        the dense engine). One dispatch per optimizer step regardless of
        gas — the scan body is traced once."""
        sh = self._state_shardings()
        apply_step = self._make_apply_step()
        micro_acc = self._make_micro_accumulate()

        def run(state, lr, rngs, *args):
            # args leaves: [gas, micro_global, ...] — dim 1 dp-sharded
            def micro_body(carry, sl):
                acc, loss = micro_acc(state["params"], carry,
                                      state["loss_scale"], sl[0], sl[1:])
                return acc, loss

            acc, losses = jax.lax.scan(
                micro_body, state["acc_grads"], (rngs,) + args)
            new_state, gnorm, overflow = apply_step(
                {**state, "acc_grads": acc}, lr)
            return new_state, jnp.mean(losses), gnorm, overflow

        scalar = NamedSharding(self.mesh, P())
        self._jit_train_batch = jax.jit(
            run, donate_argnums=(0,),
            out_shardings=(dict(sh), scalar, scalar, scalar))

    def train_batch(self, data_iter=None, data=None, batch=None):
        """Reference ``train_batch`` surface (``data_iter``/``data`` match
        PipelineEngine.train_batch): consume ``gas`` micro-batches — from
        ``data_iter``, or pre-stacked arrays (leading gas dim) via
        ``data``/``batch`` — run them and the optimizer step as ONE
        compiled program, and return the mean loss.

        Falls back to the fwd/bwd/step loop for engines whose micro path
        is specialised (1-bit, ZeRO++ quantized, offload transfers).
        """
        gas = int(self.config.gradient_accumulation_steps)
        if self.micro_steps % gas != 0 or self._pending_step is not None \
                or self._accum_pending \
                or (self._last_loss is not None
                    and not self._seen_backward):
            raise RuntimeError(
                f"train_batch called mid-accumulation (micro_steps="
                f"{self.micro_steps}, gas={gas}, pending forward="
                f"{not self._seen_backward}): finish the pending "
                f"forward/backward/step sequence first")
        if batch is None:
            batch = data
        if batch is None:
            if data_iter is None:
                raise ValueError("train_batch needs data_iter or batch")
            micros = [next(data_iter) for _ in range(gas)]
            micros = [m if isinstance(m, (tuple, list)) else (m,)
                      for m in micros]
            batch = tuple(
                np.stack([np.asarray(m[i]) for m in micros])
                for i in range(len(micros[0])))
        zc = self.config.zero_config
        scan_unsupported = (
            self._onebit or self._offload_plan is not None
            or bool(self._offload_device)
            or bool(self._offload_param_device)
            or zc.zero_quantized_gradients
            or (zc.zero_quantized_weights and self.zero_stage >= 3)
            # profiler/breakdown instrument the per-micro programs, which
            # the single scanned program cannot attribute
            or self.config.flops_profiler.enabled
            or self.config.wall_clock_breakdown)
        if scan_unsupported:
            losses = []
            for g in range(gas):
                sl = tuple(leaf[g] for leaf in batch)
                loss = self.forward(*sl)
                self.backward(loss)
                self.step()
                losses.append(loss)
            return jnp.mean(jnp.stack([jnp.asarray(l) for l in losses]))
        if self.state is None:
            self.initialize_parameters(*(leaf[0] for leaf in batch))
        if self._jit_train_batch is None:
            self._build_train_batch()
        def place(leaf):
            # micro-batch sharding (honours a custom batch_spec) with a
            # replicated leading gas axis
            if getattr(leaf, "ndim", 0) < 2:
                return jax.device_put(leaf, NamedSharding(self.mesh, P()))
            micro_sharding = self.batch_sharding(leaf[0])
            spec = P(None, *tuple(micro_sharding.spec))
            return jax.device_put(leaf, NamedSharding(self.mesh, spec))

        placed = tuple(place(leaf) for leaf in batch)
        self._rng, sub = jax.random.split(self._rng)
        rngs = jax.random.split(sub, gas)
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        self.tput_timer.start()
        self.state, loss, gnorm, overflow = self._jit_train_batch(
            self.state, lr, rngs, *placed)
        self._last_loss = loss
        self._seen_backward = True  # the cycle is complete, nothing pending
        self.micro_steps += gas
        self.global_samples += self.config.train_micro_batch_size_per_gpu \
            * self.dp_world_size * gas
        self._post_step_bookkeeping(overflow)
        return loss

    def _build_eval(self):
        def ev(params, rng, *args):
            return self._apply_fn(params, *args, rng=rng, train=False)

        self._jit_eval = jax.jit(ev)

    # ------------------------------------------------------------------ #
    # Reference API: forward / backward / step
    # ------------------------------------------------------------------ #
    def __call__(self, *args, **kwargs):
        return self.forward(*args, **kwargs)

    def forward(self, *args):
        """Training: computes loss AND gradients in one device program; eval:
        pure forward. (reference engine.forward:1772)"""
        if self.state is None:
            self.initialize_parameters(*args)
        args = self.shard_batch(args)
        self._param_offload_transfer(to_host=False)
        self._rng, rng = jax.random.split(self._rng)
        if not self.training:
            if self._jit_eval is None:
                self._build_eval()
            return self._jit_eval(self.state["params"], rng, *args)
        if self._jit_micro is None and self._jit_fused is None:
            if self._can_fuse_step():
                self._build_fused_step()
            else:
                self._build_micro()
        if self.micro_steps % self.config.gradient_accumulation_steps == 0:
            self.tput_timer.start()
        if self._jit_fused is not None:
            # one program: loss+grads+optimizer (see _can_fuse_step)
            if self._pending_step is not None:
                raise RuntimeError(
                    "fused step: at gradient_accumulation_steps=1 every "
                    "forward() applies the optimizer update — call "
                    "backward() and step() before the next forward() "
                    "(use engine.eval() to compute a loss without "
                    "updating)")
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            if self._fused_in_shapes is None:
                # abstract input shapes let capture_memory_ledger()
                # re-lower this exact program later without holding (or
                # donating) live state
                self._fused_in_shapes = jax.tree.map(
                    _abstract, (self.state, lr, rng) + args)
            self.timers(FORWARD_MICRO_TIMER).start()
            self.state, loss, gnorm, overflow = self._jit_fused(
                self.state, lr, rng, *args)
            self.timers(FORWARD_MICRO_TIMER).stop(
                sync_obj=loss if self.config.wall_clock_breakdown else None)
            self._pending_step = (gnorm, overflow)
            self._last_loss = loss
            self._seen_backward = False
            return loss
        self.timers(FORWARD_MICRO_TIMER).start()
        inputs = (self.state["params"], self.state["acc_grads"],
                  self.state["loss_scale"], rng) + args
        if self._micro_in_shapes is None:
            self._micro_in_shapes = jax.tree.map(_abstract, inputs)
        micro_fn = self._jit_micro
        if self.config.flops_profiler.enabled:
            # AOT-compile once and reuse the executable for both execution
            # and the profiler's cost_analysis — no duplicate compile at
            # profile_step. A shape change (e.g. a final partial batch)
            # falls back to the retracing jit path.
            if self._micro_compiled is None:
                self._micro_compiled = self._jit_micro.lower(
                    *self._micro_in_shapes).compile()
            if _shapes_match(inputs, self._micro_in_shapes):
                micro_fn = self._micro_compiled
        self.state["acc_grads"], loss = micro_fn(*inputs)
        self.timers(FORWARD_MICRO_TIMER).stop(
            sync_obj=loss if self.config.wall_clock_breakdown else None)
        self._last_loss = loss
        self._seen_backward = False
        return loss

    def backward(self, loss, retain_graph: bool = False):
        """Gradients were produced by ``forward``; this keeps the reference's
        call shape and advances the micro-step clock.
        (reference engine.backward:1913)"""
        del retain_graph
        if self._seen_backward:
            raise RuntimeError("backward() called twice for one forward()")
        self._seen_backward = True
        self._accum_pending = True
        self.micro_steps += 1
        self.global_samples += self.config.train_micro_batch_size_per_gpu * \
            self.dp_world_size
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self.micro_steps % self.config.gradient_accumulation_steps == 0

    def get_lr(self):
        """LR that the *next* optimizer step will apply. Derived from the
        engine's step counter without mutating the scheduler, so
        ``scheduler.get_last_lr()`` (updated by ``scheduler.step``) and this
        stay consistent."""
        if self.lr_scheduler is not None:
            return [float(self.lr_scheduler.lr_fn(self.global_steps))]
        return [self._base_lr]

    def _offload_transfer(self, to_host: bool):
        """Stream offloaded master/opt leaves host<->device at the
        optimizer-step boundary (the reference's CPU-Adam H2D/D2H cadence,
        zero/parameter_offload.py)."""
        plan, sh = self._offload_plan, self._shardings
        self.state["master"] = plan.place(self.state["master"], sh["master"],
                                          to_host=to_host,
                                          swap_prefix="master")
        self.state["opt"] = {
            k: plan.place(v, sh["opt"][k], to_host=to_host,
                          swap_prefix=f"opt_{k}")
            for k, v in self.state["opt"].items()}

    def _param_offload_transfer(self, to_host: bool):
        """Stream the compute-precision params host<->device
        (offload_param — ZeRO-Infinity's param tier at host granularity:
        HBM holds params only while a program runs)."""
        if self._param_offload_plan is None or \
                self._params_on_host == to_host:
            return
        self.state["params"] = self._param_offload_plan.place(
            self.state["params"], self._shardings["params"],
            to_host=to_host, swap_prefix="params")
        self._params_on_host = to_host

    def step(self):
        """Optimizer step at gradient-accumulation boundaries.
        (reference engine.step:2111 -> _take_model_step:2045)"""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._pending_step is not None:
            return self._finish_fused_step()
        if self._onebit_compression_stage():
            return self._onebit_step()
        if self._offload_plan is not None and self._offload_pipeline \
                and not self._onebit:
            # pipelined host-Adam: per-bucket H2D/update/D2H streams in
            # place of the synchronous whole-tree placement boundary
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            self.timers(STEP_MICRO_TIMER).start()
            gnorm, overflow = self._pipelined_offload_step(lr)
            self.timers(STEP_MICRO_TIMER).stop(
                sync_obj=self.state["loss_scale"]
                if self.config.wall_clock_breakdown else None)
            self._post_step_bookkeeping(overflow)
            return gnorm
        if self._jit_apply is None:
            self._build_apply()
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        self.timers(STEP_MICRO_TIMER).start()
        if self._offload_plan is not None:
            self._offload_transfer(to_host=False)
        apply_fn = self._jit_apply
        if self.config.flops_profiler.enabled:
            if self._apply_compiled is None:
                state_sh = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=getattr(x, "sharding", None)), self.state)
                lr_sh = jax.ShapeDtypeStruct(
                    (), jnp.float32, sharding=NamedSharding(self.mesh, P()))
                self._apply_compiled = self._jit_apply.lower(
                    state_sh, lr_sh).compile()
                self._apply_in_shapes = (state_sh, lr_sh)
            if _shapes_match((self.state, lr), self._apply_in_shapes):
                apply_fn = self._apply_compiled
        self.state, gnorm, overflow = apply_fn(self.state, lr)
        if self._offload_plan is not None:
            self._offload_transfer(to_host=True)
        self.timers(STEP_MICRO_TIMER).stop(
            sync_obj=self.state["loss_scale"]
            if self.config.wall_clock_breakdown else None)
        self._post_step_bookkeeping(overflow)
        return gnorm

    def _post_step_bookkeeping(self, overflow) -> None:
        """Shared tail of every optimizer-step flavour (standard, fused,
        1-bit): throughput accounting, step counters, data-efficiency
        schedules, overflow logging, lr schedule, periodic reporting."""
        # Sync only at reporting boundaries: intermediate steps time
        # dispatch but the window total stays exact, and async overlap is
        # preserved.
        tput_sync = (self.config.wall_clock_breakdown
                     or (self.tput_timer.global_step_count + 1)
                     % self.tput_timer.steps_per_output == 0)
        self.tput_timer.stop(
            global_step=True,
            sync_obj=self.state["loss_scale"] if tput_sync else None)
        self._param_offload_transfer(to_host=True)
        self.global_steps += 1
        self._accum_pending = False
        self._update_data_efficiency()
        self._maybe_profile_flops()
        if self.fp16_enabled:
            # Accumulate the overflow flag ON DEVICE: the add dispatches
            # asynchronously, where the previous bool(jax.device_get(..))
            # blocked the host on the device EVERY step (dslint
            # step-host-sync). The tally is fetched only at reporting
            # boundaries / checkpointing via the skipped_steps property.
            flag = jnp.asarray(overflow).astype(jnp.int32)
            self._overflow_accum = flag if self._overflow_accum is None \
                else self._overflow_accum + flag
        if self.lr_scheduler is not None:
            self.lr_scheduler.step(self.global_steps)
        if self.global_steps % self.config.steps_per_print == 0:
            if self.fp16_enabled:
                self._log_fp16_skips()
            if self.config.wall_clock_breakdown:
                self.timers.log([FORWARD_MICRO_TIMER, STEP_MICRO_TIMER],
                                memory_breakdown=True)
            if self.monitor.enabled:
                self.monitor.write_events([
                    ("Train/lr", self.get_lr()[0], self.global_steps),
                    ("Train/samples_per_sec",
                     self.tput_timer.avg_samples_per_sec(),
                     self.global_steps)])

    def _log_fp16_skips(self) -> None:
        """Reporting-boundary fp16 skip log: ONE sync covers the whole
        window (deliberately outside the step functions so the dslint
        step-host-sync rule keeps the hot path honest)."""
        skipped = self.skipped_steps
        if skipped > self._skipped_steps_logged:
            log_dist(
                f"step {self.global_steps}: "
                f"{skipped - self._skipped_steps_logged} fp16 overflow "
                f"step(s) skipped since last report (loss scale -> "
                f"{float(jax.device_get(self.state['loss_scale']))})",
                ranks=[0])
        self._skipped_steps_logged = skipped

    def lower_train_step(self):
        """The program ``forward`` dispatches — the fused
        loss+grads+optimizer step, or the micro (loss+grads) program
        when the step cannot fuse — lowered from the input shapes its
        first call recorded.  Abstract: no live state is touched or
        donated."""
        if self._fused_in_shapes is not None:
            return self._jit_fused.lower(*self._fused_in_shapes)
        if self._micro_in_shapes is not None:
            return self._jit_micro.lower(*self._micro_in_shapes)
        raise RuntimeError(
            "lower_train_step: no train program yet — run a step first")

    def capture_memory_ledger(self, ledger=None):
        """HLO memory ledger of this engine's compiled train programs
        (``memory_analysis`` + ``cost_analysis`` per program).

        Reuses the flops-profiler AOT executables when they exist;
        otherwise re-lowers the jitted micro/fused programs from their
        recorded input shapes (abstract — no live state is touched or
        donated; XLA's persistent compilation cache makes the re-compile
        cheap on bench hosts).  Backends/paths without a compiled
        program yield an explicit ``unavailable`` record — the BENCH
        JSON always carries a memory claim, even a claim of absence."""
        from deepspeed_tpu.observability.memory import MemoryLedger

        led = ledger if ledger is not None else MemoryLedger()
        meta = {
            "zero_stage": self.zero_stage,
            "micro_batch": self.config.train_micro_batch_size_per_gpu,
            "dp_world_size": self.dp_world_size,
        }
        recorded = False
        try:
            if self._micro_compiled is not None:
                led.record("train_micro", self._micro_compiled, meta=meta)
                recorded = True
            elif self._micro_in_shapes is not None:
                led.record("train_micro",
                           self.lower_train_step().compile(), meta=meta)
                recorded = True
            if self._apply_compiled is not None:
                led.record("optimizer_apply", self._apply_compiled,
                           meta=meta)
                recorded = True
            if self._fused_in_shapes is not None:
                led.record("train_fused_step",
                           self.lower_train_step().compile(), meta=meta)
                recorded = True
        except Exception as e:  # noqa: BLE001 — absence is a record
            led.record_unavailable("train_step",
                                   f"{type(e).__name__}: {e}", meta=meta)
            return led
        if not recorded:
            led.record_unavailable(
                "train_step",
                "no compiled train program yet — run a step first",
                meta=meta)
        return led

    def register_observability(self, registry,
                               key: str = "train_engine"):
        """Register host-side HBM residency gauges for the engine state
        tree (``observability/hbm_params_bytes`` etc.) as a unified-
        registry provider.  Pure shape arithmetic per scrape — no
        transfers, no syncs."""
        from deepspeed_tpu.observability.memory import tree_bytes

        def provider():
            if self.state is None:
                return {}
            out = {}
            for name in ("params", "master", "opt", "acc_grads"):
                if name in self.state:
                    out[f"observability/hbm_{name}_bytes"] = \
                        tree_bytes(self.state[name])
            if self._offload_stats is not None:
                out.update(self._offload_stats.snapshot())
            # what the process built, and how long the engine took to
            # launch its first step program
            out.update(self._build_telemetry(
                getattr(f, "__name__", None) for f in (
                    self._jit_micro, self._jit_fused, self._jit_train_batch)
                if f is not None))
            return out

        registry.register_provider(key, provider)
        return provider

    def _maybe_profile_flops(self):
        """One-shot compiler-derived flops profile at ``profile_step``
        (reference profiling/flops_profiler wired at engine.py:2182)."""
        fp = self.config.flops_profiler
        if (not fp.enabled or self.flops_profiler is not None
                or self.global_steps < fp.profile_step
                or self._micro_in_shapes is None):
            return
        from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler

        prof = FlopsProfiler(ds_engine=self,
                             recompute_fwd_factor=fp.recompute_fwd_factor)
        prof.start_profile()
        try:
            # Reuse the AOT executables forward()/step() already compiled —
            # the profile itself costs no extra compilation.
            gas = self.config.gradient_accumulation_steps
            if self._micro_compiled is not None:
                prof.profile_compiled("train_micro(fwd+bwd)",
                                      self._micro_compiled, calls=gas)
            if self._apply_compiled is not None:
                prof.profile_compiled("optimizer_step", self._apply_compiled)
        except Exception as e:  # pragma: no cover
            logger.warning(f"flops profile failed: {e}")
        prof.stop_profile()
        self.flops_profiler = prof
        prof.print_model_profile(profile_step=fp.profile_step,
                                 detailed=fp.detailed,
                                 output_file=fp.output_file)

    def _update_data_efficiency(self):
        """Advance curriculum/random-LTD/PLD schedules to the new global
        step (reference engine step hooks)."""
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self.global_steps)
        if self.random_ltd_scheduler is not None:
            self.random_ltd_scheduler.update_seq(self.global_steps)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)

    def get_data_difficulty(self) -> Optional[int]:
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.get_current_difficulty()

    def get_random_ltd_seq(self) -> Optional[int]:
        if self.random_ltd_scheduler is None:
            return None
        return self.random_ltd_scheduler.get_current_seq()

    def get_pld_theta(self) -> float:
        if self.progressive_layer_drop is None:
            return 1.0
        return self.progressive_layer_drop.get_theta()

    def _finish_fused_step(self):
        """Bookkeeping half of a step whose device work already ran inside
        the fused forward program."""
        gnorm, overflow = self._pending_step
        self._pending_step = None
        self._post_step_bookkeeping(overflow)
        return gnorm

    def _onebit_compression_stage(self) -> bool:
        return self._onebit and self.global_steps >= \
            int(self.optimizer_def.hyperparams.get("freeze_step", 0))

    def _onebit_step(self):
        """Compression-stage optimizer step: 1-bit momentum allreduce
        (reference onebit/adam.py post-freeze path)."""
        from deepspeed_tpu.runtime.fp16.onebit import build_compressed_apply

        hp = self.optimizer_def.hyperparams
        update_var = (self.optimizer_def.name == "zerooneadam" and
                      self.global_steps < int(hp.get("var_freeze_step", 0)))
        if self._jit_apply_compressed is None or \
                update_var != self._onebit_update_var:
            log_dist(
                f"1-bit {self.optimizer_def.name}: entering compression "
                f"stage at step {self.global_steps} "
                f"(update_variance={update_var})", ranks=[0])
            self._jit_apply_compressed = build_compressed_apply(
                self, update_variance=update_var)
            self._onebit_update_var = update_var
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        self.timers(STEP_MICRO_TIMER).start()
        self.state, gnorm, overflow = self._jit_apply_compressed(
            self.state, lr)
        self.timers(STEP_MICRO_TIMER).stop(
            sync_obj=self.state["loss_scale"]
            if self.config.wall_clock_breakdown else None)
        self._post_step_bookkeeping(overflow)
        return gnorm

    def train(self, mode: bool = True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    # convenience: full fwd+bwd+step over one micro batch
    def train_micro_batch(self, *args):
        loss = self.forward(*args)
        self.backward(loss)
        self.step()
        return loss

    # ------------------------------------------------------------------ #
    # Introspection (reference engine getters)
    # ------------------------------------------------------------------ #
    @property
    def params(self):
        return self.state["params"] if self.state else None

    @property
    def skipped_steps(self) -> int:
        """fp16 steps skipped on overflow. Reading this SYNCS (fetches
        the on-device overflow tally); the hot path never reads it —
        only checkpointing, reporting, and user introspection do."""
        if self._overflow_accum is None:
            return self._skipped_steps_base
        return self._skipped_steps_base + int(
            jax.device_get(self._overflow_accum))

    @skipped_steps.setter
    def skipped_steps(self, value: int) -> None:
        self._skipped_steps_base = int(value)
        self._overflow_accum = None
        self._skipped_steps_logged = int(value)

    def get_global_grad_norm(self):
        return None  # populated after step via return value

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def get_loss_scale(self) -> float:
        if self.state is None:
            return self._initial_scale
        return float(jax.device_get(self.state["loss_scale"]))

    def module_state_dict(self):
        """Consolidated host copy of model weights (fp32 master)."""
        from deepspeed_tpu.utils.tensors import tree_to_flat_dict

        return tree_to_flat_dict(jax.device_get(self.state["master"]))

    # ------------------------------------------------------------------ #
    # Checkpointing (reference engine.save_checkpoint:3021 /
    # load_checkpoint:2672)
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None,
                        save_latest: bool = True):
        from deepspeed_tpu.checkpoint.engine import save_engine_state

        if self._pending_step is not None:
            # the fused forward already applied the optimizer update; a
            # checkpoint here would persist weights one step ahead of the
            # global_steps/lr bookkeeping
            raise RuntimeError(
                "save_checkpoint called between forward() and step() with "
                "the fused step active: call step() first so the "
                "engine's step/lr bookkeeping matches the saved weights")
        tag = tag or f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
        })
        if self.lr_scheduler is not None:
            client_state["lr_scheduler"] = self.lr_scheduler.state_dict()
        save_engine_state(self, save_dir, tag, client_state,
                          save_latest=save_latest)
        return True

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_module_strict: bool = True,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        load_module_only: bool = False,
                        verify: str = "full", fallback: bool = True,
                        metrics=None):
        from deepspeed_tpu.checkpoint.engine import load_engine_state

        path, client_state = load_engine_state(
            self, load_dir, tag,
            load_optimizer_states=load_optimizer_states and not load_module_only,
            verify=verify, fallback=fallback, metrics=metrics)
        if path is None:
            return None, {}
        # the loaded state supersedes any update applied by a fused
        # init-forward; drop its pending bookkeeping
        self._pending_step = None
        if self._offload_plan is not None:
            self._offload_transfer(to_host=True)  # restore host residency
        self._params_on_host = False  # loaded arrays are device-placed
        self._param_offload_transfer(to_host=True)
        if client_state:
            self.global_steps = int(client_state.get("global_steps", 0))
            self.global_samples = int(client_state.get("global_samples", 0))
            self.micro_steps = int(client_state.get("micro_steps", 0))
            self.skipped_steps = int(client_state.get("skipped_steps", 0))
            if (load_lr_scheduler_states and self.lr_scheduler is not None
                    and "lr_scheduler" in client_state):
                self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
            # data-efficiency schedules are pure functions of global_steps:
            # re-derive them so the first post-resume batch sees the right
            # difficulty/seq/theta
            self._update_data_efficiency()
        return path, client_state
