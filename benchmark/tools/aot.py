"""Rehearsal 3 of the on-chip-measurement guide: compile each cell's step /
tick programs at REAL size for a described v5e (``v5e:2x2`` topology) from a
machine with no chip, and print XLA's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot.py train <config-name> [key=value ...]
    JAX_PLATFORMS=cpu python3 benchmark/tools/aot.py serve <config-name> [key=value ...]

``key=value`` overrides a top-level number of the configuration file, or
``serve.<key>`` / ``train.<key>`` (e.g. ``n_layer=16``,
``serve.kv_pool_blocks=700``) — that is how the GPT-2 depth and the serving
pool were sized.  Nothing runs: no value, no time.  The program routes its
kernels on ``jax.devices()[0].platform``, so this script (not the program)
points ``jax.devices`` at the described devices while it lowers, and it
reaches into the engines' lowering internals because a program that places
its own parameters cannot be handed shapes any other way.
"""

from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _CHECKOUT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from benchmark.lib import spec  # noqa: E402

def _config(name: str, overrides):
    bench = spec.benchmark_spec()
    entry = [c for c in bench["configs"] if c["name"] == name][0]
    cfg = spec.load_json(os.path.join(spec.CHECKOUT, entry["file"]))
    for kv in overrides:
        k, v = kv.split("=")
        tgt = cfg
        if "." in k:
            grp, k = k.split(".")
            tgt = cfg[grp]
        tgt[k] = type(tgt[k])(v) if k in tgt and tgt[k] is not None \
            else int(v)
    cell = [w for w in bench["workloads"] if w["config"] == name][0]
    return cfg, spec.traffic_for(cell), int(cell["chips"])


def _report(what: str, compiled, resident: float, t0: float) -> None:
    m = compiled.memory_analysis()
    args, out, temp = (m.argument_size_in_bytes, m.output_size_in_bytes,
                       m.temp_size_in_bytes)
    alias = getattr(m, "alias_size_in_bytes", 0)
    print(f"{what}: compiled in {time.time() - t0:.0f} s; per device: "
          f"arguments {args / 1e9:.2f} GB, outputs {out / 1e9:.2f} GB "
          f"(aliased {alias / 1e9:.2f}), temporaries {temp / 1e9:.2f} GB; "
          f"resident {resident / 1e9:.2f} GB + temporaries = "
          f"{(resident + temp) / 1e9:.2f} GB of 16.91 GB (15.75 GiB)",
          flush=True)


def train(name: str, overrides) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, mix, chips = _config(name, overrides)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    real_devices = jax.devices
    jax.devices = lambda *a, **k: devices          # route as on the chip
    try:
        family = spec.module("families", cfg["family"])
        from benchmark.runners.train_engine import _ds_config

        tr = cfg["train"]
        groups.reset()
        mesh_topo = groups.initialize_mesh(
            model_parallel_size=int(tr["mesh"]["model"]),
            data_parallel_size=int(tr["mesh"]["data"]), devices=devices)
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=family.train_model(cfg), config=_ds_config(tr),
            topology=mesh_topo)
        b, s = int(mix["global_batch"]), int(mix["seq_len"])
        ids = jax.ShapeDtypeStruct((b, s), jnp.int32)
        rng = jax.random.key(0)
        shapes = jax.eval_shape(engine._init_fn, rng, ids, ids)
        sh = dict(engine._build_shardings(shapes))
        state = jax.eval_shape(
            lambda r, x: engine._make_state(jax.tree.map(
                lambda p: p.astype(jnp.float32), engine._init_fn(r, x, x))),
            rng, ids)
        state = jax.tree.map(
            lambda x, s_: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s_),
            state, sh)
        engine._shardings = sh
        engine._build_fused_step()
        scalar = NamedSharding(engine.mesh, P())
        batch_sh = engine.batch_sharding(ids)
        args = (state,
                jax.ShapeDtypeStruct((), jnp.float32, sharding=scalar),
                jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=scalar),
                jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=batch_sh),
                jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=batch_sh))
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(shapes))
        t0 = time.time()
        lowered = engine._jit_fused.lower(*args)
        text = lowered.as_text()
        import re
        kernels = re.findall(r'kernel_name = "([^"]+)"', text)
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        print(f"{name}: {n_params / 1e6:.1f}M parameters, {b}x{s} tokens, "
              f"kernels {sorted(set(kernels))}")
        # the state is donated: arguments are the resident bytes
        _report("train step", compiled, m.argument_size_in_bytes, t0)
        hlo = compiled.as_text()
        colls = {c: len(re.findall(rf"\b{c}(-start)?\(", hlo)) for c in
                 ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")}
        print(f"collectives in the compiled step: {colls}")
    finally:
        jax.devices = real_devices


def serve(name: str, overrides) -> None:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceEngineConfig)

    cfg, _mix, _chips = _config(name, overrides)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    shapes = family.shapes(cfg)
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one),
        family.serve_param_shapes(cfg))
    weight_bytes = sum(int(np.prod(l.shape)) * 2
                       for l in jax.tree_util.tree_leaves(params))
    # a tiny real pool to construct the engine; the lowered programs take
    # the pool as an argument, at the real size
    eng_cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {
            "max_ragged_batch_size": sv["token_budget"],
            "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
            "max_context": sv["max_context"]},
        "kv_cache": {"block_size": sv["block_size"], "num_blocks": 4}})
    engine = InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])), params, eng_cfg)
    rows = int(sv["kv_pool_blocks"]) * int(sv["block_size"])
    cache = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((rows,) + a.shape[1:], a.dtype,
                                       sharding=one),
        engine.state_manager.kv_cache.cache)
    pool_bytes = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                     for l in jax.tree_util.tree_leaves(cache))
    resident = weight_bytes + pool_bytes
    print(f"{name}: weights {weight_bytes / 1e9:.2f} GB, pool "
          f"{sv['kv_pool_blocks']} blocks = {rows} tokens = "
          f"{pool_bytes / 1e9:.2f} GB "
          f"({shapes['kv_bytes_per_token']} B/token)")
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // int(sv["block_size"]))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]
    try:
        budget = int(sv["token_budget"])
        keys = ["decode_step"] + [(b, None) for b in engine._buckets] + \
            [(b, 128) for b in sorted(
                {b for b in engine._buckets if b % 128 == 0} | {128})
             if b <= budget]
        for key in keys:
            t0 = time.time()
            if key == "decode_step":
                fn = engine._get_decode_step()
                args = (ints(S, B), ints(S), ints(S))
            else:
                fn = engine._get_step(*key)
                args = (ints(4 * key[0] + S * B + 2 * S),)
            compiled = fn.lower(params, cache, *args).compile()
            _report(f"  {key}", compiled, resident, t0)
    finally:
        jax.devices = real_devices


if __name__ == "__main__":
    {"train": train, "serve": serve}[sys.argv[1]](sys.argv[2], sys.argv[3:])
