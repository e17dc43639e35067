"""PR 27: are the programs of the cells the same text in two trees?

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr27_program_text.py <tree>

Lowers, at the cells' real sizes for a described v5e (no chip: section 2 of
the on-chip-measurement guide, through ``benchmark/tools/aot.py``'s own
set-up), the Mistral-7B serving ``decode_step`` and one tiled ``put``
program, the GPT-2-Large fused step and the Mistral-7B ZeRO-3 x TP fused
step of ``<tree>`` (a checkout: the parent's ``git archive`` or this one),
and prints for each the sha256 of ``lowered.as_text()`` with every Mosaic
kernel's serialised body replaced by the hash of its assembly without debug
information (the body carries the lowering tree's paths and line numbers),
its length and its count of ``optimization_barrier``.  The four-chip step
is also compiled, and ``aot.py`` prints its ``memory_analysis()``.  Nothing runs: no
value, no time.
"""

import base64
import hashlib
import os
import re
import sys

tree = os.path.abspath(sys.argv[1])
os.chdir(tree)
sys.path.insert(0, tree)

from benchmark.tools import aot          # noqa: E402  (sets the TPU env)
import jax                               # noqa: E402


_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _kernel_asm(b64):
    """A Mosaic kernel travels as serialised MLIR with the call stack of its
    call site in it (file paths and line numbers of the tree that lowered
    it): compare its assembly without debug information instead."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(b64))
        return module.operation.get_asm(enable_debug_info=False)


def report(name, lowered):
    raw = lowered.as_text()
    kernels = []

    def swap(m):
        asm = _kernel_asm(m.group(2))
        kernels.append(asm)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest() \
            + m.group(3)

    text = _BODY.sub(swap, raw)
    print(f"{name}: sha256 {hashlib.sha256(text.encode()).hexdigest()[:16]} "
          f"(as lowered: {hashlib.sha256(raw.encode()).hexdigest()[:16]}) "
          f"chars {len(text)} kernels {len(kernels)} optimization_barrier "
          f"{text.count('optimization_barrier')}", flush=True)


class _Lowered:
    """Stands in for a jitted program inside ``aot``: reports the lowered
    text and stops ``aot`` from compiling unless asked to."""

    def __init__(self, fn, name, compile_it):
        self.fn, self.name, self.compile_it = fn, name, compile_it

    def lower(self, *args):
        lowered = self.fn.lower(*args)
        report(self.name, lowered)
        if not self.compile_it:
            raise _Done
        return lowered


class _Done(Exception):
    pass


def train(config, compile_it):
    import deepspeed_tpu

    real = deepspeed_tpu.runtime.engine.DeepSpeedEngine._build_fused_step

    def build(self):
        real(self)
        self._jit_fused = _Lowered(self._jit_fused, f"{config} fused step",
                                   compile_it)

    deepspeed_tpu.runtime.engine.DeepSpeedEngine._build_fused_step = build
    try:
        aot.train(config, [])
    except _Done:
        pass
    finally:
        deepspeed_tpu.runtime.engine.DeepSpeedEngine._build_fused_step = real


def serve(config):
    """``aot.serve``'s set-up, with the keys a tiled engine really builds
    (``aot.py`` still lists the ladder of before PR 24: PERF.md section 7)."""
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import spec
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cfg, _mix, _chips = aot._config(config, [])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one),
        family.serve_param_shapes(cfg))
    engine = InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": sv["token_budget"],
                "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"], "num_blocks": 4}}))
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((rows,) + a.shape[1:], a.dtype,
                                       sharding=one),
        engine.state_manager.kv_cache.cache)
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // int(sv["block_size"]))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]
    try:
        report(f"{config} decode_step", engine._get_decode_step().lower(
            params, cache, ints(S, B), ints(S), ints(S)))
        for key in ((S, 128), (S + 256, 128)):
            report(f"{config} put {key}", engine._get_step(*key).lower(
                params, cache, ints(4 * key[0] + S * B + 2 * S)))
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    print(f"tree {tree}; jax {jax.__version__}")
    serve("mistral-7b-v0.1-serve-1chip")
    train("gpt2-large-train-1chip", False)
    train("mistral-7b-v0.1-train-z3tp-4chip", True)
