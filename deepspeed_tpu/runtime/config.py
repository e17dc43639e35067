"""DeepSpeed-compatible JSON config (reference: runtime/config.py:696
``DeepSpeedConfig``).

The JSON schema mirrors the reference so existing configs are recognisable:
batch trio, ``optimizer``/``scheduler`` blocks, ``fp16``/``bf16``,
``zero_optimization``, ``gradient_clipping``, monitors, profilers. Keys whose
CUDA semantics have no TPU meaning are accepted and mapped to their XLA
equivalent (documented per-field) so configs written for the reference run
unchanged.
"""

from __future__ import annotations

import copy
import dataclasses
import json
from typing import Any, Dict, List, Optional

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import (
    DeepSpeedConfigModel,
    config_field,
)
from deepspeed_tpu.utils.logging import logger

AUTO = "auto"


# --------------------------------------------------------------------- #
# Subsystem configs
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class FP16Config(DeepSpeedConfigModel):
    """reference: fp16 block (runtime/fp16/*). Dynamic loss scaling state
    lives in the jitted step (lax.cond), not host code."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = C.FP16_LOSS_SCALE_DEFAULT
    initial_scale_power: int = C.FP16_INITIAL_SCALE_POWER_DEFAULT
    loss_scale_window: int = C.FP16_LOSS_SCALE_WINDOW_DEFAULT
    hysteresis: int = C.FP16_HYSTERESIS_DEFAULT
    consecutive_hysteresis: bool = False
    min_loss_scale: float = C.FP16_MIN_LOSS_SCALE_DEFAULT
    fp16_master_weights_and_grads: bool = False


@dataclasses.dataclass
class BF16Config(DeepSpeedConfigModel):
    """reference: bf16 block (runtime/bf16_optimizer.py). On TPU this is the
    native precision: bf16 compute params + fp32 master/grad accumulation."""

    enabled: bool = False
    accumulate_grads_in_fp32: bool = True


@dataclasses.dataclass
class OptimizerConfig(DeepSpeedConfigModel):
    type: str = C.ADAMW_OPTIMIZER
    params: Dict[str, Any] = config_field(default_factory=dict)
    legacy_fusion: bool = False


@dataclasses.dataclass
class SchedulerConfig(DeepSpeedConfigModel):
    type: Optional[str] = None
    params: Dict[str, Any] = config_field(default_factory=dict)


@dataclasses.dataclass
class OffloadParamConfig(DeepSpeedConfigModel):
    """reference: zero/offload_config.py DeepSpeedZeroOffloadParamConfig."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False


@dataclasses.dataclass
class OffloadOptimizerConfig(DeepSpeedConfigModel):
    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    #: pipelined offload: number of per-bucket streams the optimizer
    #: update is split into (the reference's aio buffer_count — here the
    #: in-flight H2D/update/D2H slots of the pipelined host-Adam path)
    buffer_count: int = 4
    pin_memory: bool = False
    #: pipeline / pipeline_read / pipeline_write (reference cpu-adam
    #: pipelining knobs): any of them enables the per-bucket pipelined
    #: step — bucket k's update runs while bucket k+1's master/opt
    #: stream H2D and bucket k-1's results stream back to pinned_host
    pipeline: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    #: opt-in diagnostics: block per bucket transfer and record its
    #: latency (adds host syncs — off on the hot path, used by the
    #: offload A/B bench to report p50/p95 transfer latency)
    profile_transfers: bool = False
    fast_init: bool = False
    ratio: float = 1.0  # ZeRO-Offload++ twin-flow partial offload

    @property
    def pipeline_enabled(self) -> bool:
        return bool(self.pipeline or self.pipeline_read
                    or self.pipeline_write)


@dataclasses.dataclass
class ZeroConfig(DeepSpeedConfigModel):
    """reference: zero/config.py DeepSpeedZeroConfig.

    TPU mapping: stages are sharding policies over the ZeRO mesh axes
    ('dout','data','seq','expert') —
      0: params/grads/optim replicated;
      1: optimizer state (incl. fp32 master) sharded;
      2: + gradients reduce-scattered and kept sharded;
      3: + parameters sharded (gathered on use by XLA).
    Accepted for config parity and read by nothing (XLA's scheduler places
    and overlaps the collectives): ``overlap_comm``, ``reduce_bucket_size``,
    ``allgather_bucket_size``, ``prefetch_bucket_size``,
    ``max_live_parameters``, ``max_reuse_distance``,
    ``contiguous_gradients``, ``reduce_scatter``, ``allgather_partitions``,
    ``round_robin_gradients``, ``ignore_unused_parameters``,
    ``sub_group_size``, ``model_persistence_threshold``,
    ``memory_efficient_linear``.
    """

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_partitions: bool = True
    allgather_bucket_size: int = int(5e8)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[OffloadParamConfig] = None
    offload_optimizer: Optional[OffloadOptimizerConfig] = None
    sub_group_size: int = int(1e9)
    cpu_offload: bool = config_field(False, deprecated=True,
                                     new_param="offload_optimizer")
    cpu_offload_params: bool = config_field(False, deprecated=True,
                                            new_param="offload_param")
    prefetch_bucket_size: int = config_field(int(5e7),
                                             aliases=("stage3_prefetch_bucket_size",))
    param_persistence_threshold: int = config_field(
        int(1e5), aliases=("stage3_param_persistence_threshold",))
    model_persistence_threshold: int = config_field(
        int(1e14), aliases=("stage3_model_persistence_threshold",))
    max_live_parameters: int = config_field(
        int(1e9), aliases=("stage3_max_live_parameters",))
    max_reuse_distance: int = config_field(
        int(1e9), aliases=("stage3_max_reuse_distance",))
    gather_16bit_weights_on_model_save: bool = config_field(
        False, aliases=("stage3_gather_16bit_weights_on_model_save",))
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    # ZeRO++ (reference zero/config.py zero_hpz/zero_quantized_*)
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_nontrainable_weights: bool = False
    zero_quantized_gradients: bool = False
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    memory_efficient_linear: bool = True

    def _validate(self) -> None:
        if not (0 <= self.stage <= 3):
            raise ValueError(f"zero_optimization.stage must be 0-3, got {self.stage}")


@dataclasses.dataclass
class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """reference: activation_checkpointing block
    (runtime/activation_checkpointing/checkpointing.py:1070 configure).

    TPU mapping: ``jax.checkpoint`` (remat) with a dots-saveable policy;
    ``partition_activations`` maps to rematerialising with activations sharded
    over the sequence/model axes; ``cpu_checkpointing`` to host offload of
    residuals via remat policy with offload (jax.ad_checkpoint offload
    policies)."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False


@dataclasses.dataclass
class CommsConfig(DeepSpeedConfigModel):
    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = config_field(default_factory=list)


@dataclasses.dataclass
class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclasses.dataclass
class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


@dataclasses.dataclass
class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


@dataclasses.dataclass
class FlopsProfilerConfig(DeepSpeedConfigModel):
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclasses.dataclass
class PipelineConfig(DeepSpeedConfigModel):
    """reference: pipeline block (engine.py pipeline config)."""

    stages: Any = "auto"
    partition: str = "best"
    seed_layers: bool = False
    activation_checkpoint_interval: int = 0
    use_reentrant: bool = True


@dataclasses.dataclass
class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False


@dataclasses.dataclass
class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


@dataclasses.dataclass
class AioConfig(DeepSpeedConfigModel):
    """reference: aio block (csrc/aio). Maps to the host-side C++ async file
    I/O library used for NVMe offload."""

    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


# --------------------------------------------------------------------- #
# Top-level config
# --------------------------------------------------------------------- #
class DeepSpeedConfig:
    """Parsed top-level config (reference runtime/config.py:696).

    Accepts a dict or a path to a JSON file. Batch-trio resolution follows
    the reference exactly: ``train_batch_size = micro_batch_per_gpu *
    gradient_accumulation_steps * dp_world_size``.
    """

    def __init__(self, config: Any, mpu=None, mesh=None):
        if isinstance(config, str):
            with open(config, "r") as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = copy.deepcopy(config)
        else:
            raise ValueError(f"config must be dict or path, got {type(config)}")

        pd = self._param_dict
        self.train_batch_size = pd.get(C.TRAIN_BATCH_SIZE)
        self.train_micro_batch_size_per_gpu = pd.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        self.gradient_accumulation_steps = pd.get(C.GRADIENT_ACCUMULATION_STEPS)
        self.steps_per_print = pd.get("steps_per_print", C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = pd.get("dump_state", False)
        self.gradient_clipping = float(pd.get("gradient_clipping",
                                              C.GRADIENT_CLIPPING_DEFAULT))
        self.prescale_gradients = pd.get("prescale_gradients", False)
        self.gradient_predivide_factor = pd.get("gradient_predivide_factor", 1.0)
        self.sparse_gradients_enabled = pd.get("sparse_gradients", False)
        self.communication_data_type = pd.get("communication_data_type", None)
        self.seq_parallel_communication_data_type = pd.get(
            "seq_parallel_communication_data_type", "fp32")
        self.disable_allgather = pd.get("disable_allgather", False)
        self.wall_clock_breakdown = pd.get("wall_clock_breakdown", False)
        self.memory_breakdown = pd.get("memory_breakdown", False)
        self.dataloader_drop_last = pd.get("dataloader_drop_last", False)
        self.seed = pd.get("seed", 1234)

        self.fp16 = FP16Config.from_dict(pd.get("fp16"))
        self.bf16 = BF16Config.from_dict(pd.get("bf16", pd.get("bfloat16")))
        self.optimizer = (OptimizerConfig.from_dict(pd["optimizer"])
                          if "optimizer" in pd else None)
        self.scheduler = (SchedulerConfig.from_dict(pd["scheduler"])
                          if "scheduler" in pd else None)
        self.zero_config = ZeroConfig.from_dict(pd.get("zero_optimization"))
        self.activation_checkpointing = ActivationCheckpointingConfig.from_dict(
            pd.get("activation_checkpointing"))
        self.comms_config = CommsConfig.from_dict(pd.get("comms_logger"))
        self.tensorboard = TensorBoardConfig.from_dict(pd.get("tensorboard"))
        self.wandb = WandbConfig.from_dict(pd.get("wandb"))
        self.csv_monitor = CSVConfig.from_dict(pd.get("csv_monitor"))
        self.flops_profiler = FlopsProfilerConfig.from_dict(pd.get("flops_profiler"))
        self.pipeline = PipelineConfig.from_dict(pd.get("pipeline"))
        self.checkpoint_config = CheckpointConfig.from_dict(pd.get("checkpoint"))
        self.data_types = DataTypesConfig.from_dict(pd.get("data_types"))
        self.aio = AioConfig.from_dict(pd.get("aio"))
        self.zero_allow_untested_optimizer = pd.get(
            "zero_allow_untested_optimizer", False)
        self.zero_force_ds_cpu_optimizer = pd.get("zero_force_ds_cpu_optimizer", True)
        self.compile_config = pd.get("compile", {})
        self.elasticity = pd.get("elasticity", {})
        self.autotuning = pd.get("autotuning", {})
        self.curriculum_learning = pd.get("curriculum_learning", {})
        self.data_efficiency = pd.get("data_efficiency", {})
        self.progressive_layer_drop = pd.get("progressive_layer_drop", {})
        self.hybrid_engine = pd.get("hybrid_engine", {})
        # single fused micro+apply program at gas=1 (set False to keep the
        # split programs, e.g. to inspect the micro's cost analysis)
        self.fuse_optimizer_step = bool(pd.get("fuse_optimizer_step", True))
        self.compression_config = pd.get("compression_training", {})
        self.monitor_config = None  # assembled by MonitorMaster

    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def precision_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    @property
    def loss_scale_enabled(self) -> bool:
        return self.fp16.enabled

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.fp16.enabled and self.fp16.loss_scale == 0

    def resolve_batch_size(self, dp_world_size: int,
                           world_size: int = 0) -> None:
        """Batch trio algebra (reference runtime/config.py
        ``_configure_train_batch_size``): any two of
        {train_batch_size, micro_batch, gas} determine the third.

        ``world_size`` is the TOTAL device count (dp × mp × ...), which
        elasticity v0.2 consumes; defaults to ``dp_world_size`` (correct
        when model parallelism is off).
        """
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        # Elasticity overrides the trio (reference runtime/config.py elastic
        # dict hook + elasticity/elasticity.py compute_elastic_config)
        if self.elasticity.get("enabled", False):
            from deepspeed_tpu.elasticity import (
                compute_elastic_config, ensure_immutable_elastic_config)
            from deepspeed_tpu.version import __version__

            if (tb is not None or mb is not None or gas is not None) and \
                    not self.elasticity.get("ignore_non_elastic_batch_info",
                                            False):
                raise ValueError(
                    "elasticity is enabled but batch sizes / gradient "
                    "accumulation are also set; remove them or set "
                    "elasticity.ignore_non_elastic_batch_info")
            ensure_immutable_elastic_config(self.elasticity)
            tb, _valid, mb = compute_elastic_config(
                {"elasticity": self.elasticity}, __version__,
                world_size=world_size or dp_world_size,
                return_microbatch=True)
            if mb is None:
                raise ValueError(
                    f"elasticity: batch size {tb} is not reachable with any "
                    f"declared micro_batch_sizes "
                    f"{self.elasticity.get('micro_batch_sizes')} at "
                    f"dp={dp_world_size}; change the world size or widen "
                    f"micro_batch_sizes")
            gas = tb // (mb * dp_world_size)
            logger.info(f"elasticity: train_batch_size={tb} "
                        f"micro_batch={mb} gas={gas}")
        if tb is not None and mb is not None and gas is not None:
            if tb != mb * gas * dp_world_size:
                raise ValueError(
                    f"train_batch_size {tb} != micro_batch {mb} * gas {gas} * "
                    f"dp {dp_world_size}")
        elif tb is not None and mb is not None:
            gas = tb // (mb * dp_world_size)
            if tb % (mb * dp_world_size) != 0 or gas == 0:
                raise ValueError(
                    f"train_batch_size {tb} not divisible by micro_batch {mb} * "
                    f"dp {dp_world_size}")
        elif tb is not None and gas is not None:
            if tb % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {tb} not divisible by gas {gas} * "
                    f"dp {dp_world_size}")
            mb = tb // (gas * dp_world_size)
        elif mb is not None:
            gas = gas or 1
            tb = mb * gas * dp_world_size
        elif tb is not None:
            gas = 1
            if tb % dp_world_size != 0:
                raise ValueError(
                    f"train_batch_size {tb} not divisible by dp {dp_world_size}")
            mb = tb // dp_world_size
        else:
            raise ValueError(
                "one of train_batch_size / train_micro_batch_size_per_gpu required")
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas

    def print_config(self) -> None:
        logger.info(json.dumps(self._param_dict, indent=2, sort_keys=True,
                               default=str))
