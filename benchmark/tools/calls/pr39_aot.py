"""PR 39, before any chip call: compile the Trinity cell's programs
(``decode_step`` and the two-segment ``T32`` .. ``T1056_tiled``, at 32
sequence slots, both block tables) at real size for a described v5e, from a
machine with no chip; print XLA's memory analysis, the Mosaic kernels each
calls, and every copy of a whole pool the compiler put in (there should be
none).  The window group's pool is the size the state manager gives it; the
global pool is ``serve.kv_pool_blocks`` (override: ``blocks=<n>``).

    JAX_PLATFORMS=cpu python3 benchmark/tools/calls/pr39_aot.py [blocks=N] [key ...]
"""

from __future__ import annotations

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmark.tools import aot          # noqa: E402  (sets the TPU env)

import jax                               # noqa: E402
import jax.numpy as jnp                  # noqa: E402
import numpy as np                       # noqa: E402

from benchmark.lib import device, spec   # noqa: E402

CONFIG = "trinity-large-preview-serve-1chip"


def main(argv) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        packed_length

    only = [a for a in argv if "=" not in a]
    cfg, _mix, _chips = aot._config(CONFIG, [
        "serve.kv_pool_blocks=" + a.split("=")[1] for a in argv
        if a.startswith("blocks=")])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one),
        family.serve_param_shapes(cfg))
    weight_bytes = sum(int(np.prod(l.shape)) * 2
                       for l in jax.tree_util.tree_leaves(params))
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {
            "max_ragged_batch_size": sv["token_budget"],
            "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
            "max_context": sv["max_context"]},
        "kv_cache": {"block_size": sv["block_size"], "num_blocks": 4}})
    engine = InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])), params, eng_cfg)
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    sm = engine.state_manager
    kv = sm.kv_cache
    window = {f"layer_{i}" for i in kv.window_layers}
    cache = {
        name: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                (a.shape[:1] if name in window else (rows,)) + a.shape[1:],
                a.dtype, sharding=one), leaves)
        for name, leaves in kv.cache.items()}
    pool_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(cache))
    print(f"{CONFIG}: weights {weight_bytes / 1e9:.2f} GB; global pool "
          f"{sv['kv_pool_blocks']} blocks x {kv.per_token_bytes} B/token = "
          f"{rows * kv.per_token_bytes / 1e9:.2f} GB; window pool "
          f"{kv.window_blocks} blocks ({sm.window_table_bound} a sequence) "
          f"x {len(kv.window_layers)} layers = "
          f"{kv.window_pool_bytes / 1e9:.2f} GB; pools {pool_bytes / 1e9:.2f}"
          f" GB")
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // int(sv["block_size"]))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    tile = engine.PREFILL_TILE
    keys = ["decode_step"] + [S + t for t in (0, 128, 256, 512, 1024)]
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]
    try:
        for key in keys:
            if only and str(key) not in only:
                continue
            t0 = time.time()
            if key == "decode_step":
                fn = engine._get_decode_step()
                args = (ints(S, B), ints(S), ints(S), ints(S, B))
            else:
                fn = engine._get_step(key, tile)
                args = (ints(packed_length(key, S, B, win=True)),)
            lowered = fn.lower(params, cache, *args)
            print(f"  {key}: kernels "
                  f"{device.mosaic_kernels(lowered.as_text())}")
            compiled = lowered.compile()
            aot._report(f"  {key}", compiled, weight_bytes + pool_bytes, t0)
            sizes = {rows, kv.window_blocks * int(sv["block_size"])}
            whole = [l.strip()[:160] for l in compiled.as_text().splitlines()
                     if any(re.search(rf" copy\(.*bf16\[{r},", l)
                            or re.search(
                                rf"= bf16\[{r},[^\]]*\][^=]* copy\(", l)
                            for r in sizes)]
            print(f"  {key}: copies of a whole pool: {len(whole)}")
            for line in whole[:4]:
                print("     ", line)
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    main(sys.argv[1:])
