"""deepspeed_tpu — a TPU-native training & inference framework with the
capability surface of DeepSpeed (reference: deepspeed/__init__.py), built on
JAX/XLA/Pallas: ZeRO as sharding policy, pipeline/tensor/sequence/expert
parallelism over a named device mesh, fused Pallas kernels for the hot ops.
"""

from __future__ import annotations

import time as _time
from typing import Any, Callable, Optional, Tuple

_IMPORT_OPEN_NS = _time.monotonic_ns()      # ``setup/import`` opens here

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu import comm
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import MeshTopology, ParallelDims
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.version import __version__, version

dist = comm  # reference exposes deepspeed.comm as dist


def initialize(args=None,
               model: Any = None,
               optimizer: Any = None,
               model_parameters: Any = None,
               training_data: Any = None,
               lr_scheduler: Any = None,
               distributed_port: int = 29500,
               mpu: Any = None,
               dist_init_required: Optional[bool] = None,
               collate_fn: Optional[Callable] = None,
               config: Any = None,
               config_params: Any = None,
               loss_fn: Optional[Callable] = None,
               topology: Optional[MeshTopology] = None,
               base_param_specs: Any = None,
               batch_spec: Any = None,
               **engine_kwargs) -> Tuple:
    """Build the training engine (reference: deepspeed/__init__.py:64).

    Returns ``(engine, optimizer, dataloader, lr_scheduler)`` exactly like the
    reference. ``model`` is a flax Module / (init_fn, apply_fn) pair;
    ``model_parameters`` may be a param pytree (host or device) — if omitted,
    parameters are initialised *sharded* on first forward (the ``zero.Init``
    behaviour). ``mpu``/``topology`` selects the mesh; default is pure data
    parallel over all devices.
    """
    comm.init_distributed(dist_init_required=dist_init_required,
                          distributed_port=distributed_port)

    cfg = config if config is not None else config_params
    if cfg is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config is not None:
        cfg = args.deepspeed_config
    if cfg is None:
        raise ValueError("DeepSpeed config required (config= or "
                         "args.deepspeed_config)")

    if topology is None and mpu is not None and isinstance(mpu, MeshTopology):
        topology = mpu

    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    # Normalise once so dispatch sees the parsed config regardless of
    # whether the user passed a dict, a DeepSpeedConfig, or a JSON path.
    cfg = cfg if isinstance(cfg, DeepSpeedConfig) else DeepSpeedConfig(cfg)

    def _hybrid_enabled(c):
        return bool(c.hybrid_engine.get("enabled", False))

    if isinstance(model, PipelineModule):
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

        engine = PipelineEngine(model=model, config=cfg,
                                model_parameters=model_parameters,
                                loss_fn=loss_fn, topology=topology,
                                base_param_specs=base_param_specs,
                                batch_spec=batch_spec,
                                lr_scheduler=lr_scheduler,
                                **engine_kwargs)
    elif _hybrid_enabled(cfg):
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

        engine = DeepSpeedHybridEngine(model=model, config=cfg,
                                       model_parameters=model_parameters,
                                       loss_fn=loss_fn, topology=topology,
                                       base_param_specs=base_param_specs,
                                       batch_spec=batch_spec,
                                       lr_scheduler=lr_scheduler,
                                       **engine_kwargs)
    else:
        engine = DeepSpeedEngine(model=model, config=cfg,
                                 model_parameters=model_parameters,
                                 loss_fn=loss_fn, topology=topology,
                                 base_param_specs=base_param_specs,
                                 batch_spec=batch_spec,
                                 lr_scheduler=lr_scheduler,
                                 **engine_kwargs)

    dataloader = None
    if training_data is not None:
        dataloader = DeepSpeedDataLoader(
            training_data,
            batch_size=engine.config.train_micro_batch_size_per_gpu *
            engine.dp_world_size,
            collate_fn=collate_fn,
            drop_last=engine.config.dataloader_drop_last)

    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_inference(model: Any = None, config: Any = None,
                   checkpoint: Any = None, **kwargs):
    """Inference engine entry (reference: deepspeed/__init__.py:269).

    ``checkpoint`` may be a HuggingFace checkpoint directory: the model is
    built from its ``config.json`` (when ``model`` is None) and the real
    weights are loaded pre-sharded (reference ``load_model_with_checkpoint``
    via the checkpoint-json path of ``init_inference``).
    """
    from deepspeed_tpu.inference.engine import InferenceEngine

    if checkpoint is not None and model is None:
        from deepspeed_tpu.checkpoint.hf_loader import model_from_hf
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

        # normalize "bf16"/"fp16"-style aliases through the inference
        # config before they reach the model config, so the model computes
        # in the same dtype the engine casts the weights to — mirroring
        # the engine's own config-then-kwargs merge order
        import dataclasses as _dc

        if isinstance(config, DeepSpeedInferenceConfig):
            cfg_dict = _dc.asdict(config)
        else:
            cfg_dict = dict(config or {})
        if "dtype" in kwargs:
            cfg_dict["dtype"] = kwargs["dtype"]
        dtype = DeepSpeedInferenceConfig.from_dict(cfg_dict).dtype
        _arch, _cfg, model = model_from_hf(checkpoint, dtype)
    engine = InferenceEngine(model=model, config=config, **kwargs)
    if checkpoint is not None:
        engine.load_checkpoint(checkpoint)
    return engine


def add_config_arguments(parser):
    """Inject --deepspeed / --deepspeed_config argparse flags
    (reference deepspeed/__init__.py:246)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS


def init_distributed(**kwargs):
    return comm.init_distributed(**kwargs)


# ``setup/import`` closes here (the imports above, ``jax`` among them when
# the caller had not imported it), and the program's one ``jax.monitoring``
# listener goes in: from now on every executable JAX builds in this process
# leaves a ``setup/build_program`` record (observability/tracer.py, "Once a
# process")
from deepspeed_tpu.observability import tracer as _tracer  # noqa: E402

_tracer.process_began(_IMPORT_OPEN_NS)
