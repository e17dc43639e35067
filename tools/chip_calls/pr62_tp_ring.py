"""PR 62: tensor parallelism's all-reduce as ring steps under the GEMMs
(``deepspeed_tpu/parallel/tensor_overlap.py``), rehearsed without the chip
and timed on it.

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr62_tp_ring.py --aot [<checkout>] [config=<name>] [num_hidden_layers=<n>]
        no chip: compiles the step of ``train-mistral7b-z3tp-s4k`` (or of
        another training configuration; the tree of <checkout>, default
        this one) for a described v5e:2x2 and prints a hash of the LOWERED
        text, the Mosaic calls' serialized bodies cut (equal on two trees:
        the same program), the collectives of the
        scheduled program by kind and shape, what is scheduled between every
        asynchronous collective's ``-start`` and ``-done`` (a schedule, not a
        time), XLA's memory analysis and the compile seconds.  The text goes
        to ``chiprun_out/pr62/aot_<tag>.txt``.
    python3 tools/chip_calls/pr62_tp_ring.py --sublayer
        on four chips: a Mistral-7B layer's two sublayers alone (the MLP,
        14,336 wide; attention's projections around a stand-in for the
        kernels), two layers each, forward + backward, ``[2, 4096, 4096]``
        bf16 over ``data``=2 x ``model``=2, GSPMD's all-reduce against the
        ring (1, 2 and 4 pieces a hop of the scatter; the MLP also as the
        two helpers with its ``[B, T, F]`` intermediates put together), by
        the profile's device time an execution.
    python3 tools/chip_calls/pr62_tp_ring.py --sublayer 512 1024 2048 4096
        the same two sublayers at those sequence lengths (chunks of half as
        many rows a rank), GSPMD against the ring in two pieces a hop: where
        ``tensor_overlap.MIN_CHUNK_ROWS`` comes from.
    python3 tools/chip_calls/pr62_tp_ring.py --permute
        on four chips: a hop's 16 MB alone, as 1-8 ``ppermute``s in flight,
        beside the all-reduce, ``psum_scatter`` and ``all_gather``.
    python3 tools/chip_calls/pr62_tp_ring.py --trace-ops <checkout> <steps> <out.json>
        reads the newest trace of the cell under <checkout>/bench_out and
        splits the step's collectives by kind, shape and scope, all and
        exposed (``pr26_trace_ops.py`` with the scope beside the shape).
"""

from __future__ import annotations

import collections
import glob
import hashlib
import json
import os
import re
import sys
import time

AOT = "--aot" in sys.argv
if AOT:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "train-mistral7b-z3tp-s4k"
CONFIG = "mistral-7b-v0.1-train-z3tp-4chip"
OUT = os.path.join(HERE, "chiprun_out", "pr62")

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def schedule_report(hlo: str) -> dict:
    """Collectives of a compiled, scheduled module by kind and result
    shape, synchronous or asynchronous, and for the asynchronous ones the
    instructions between ``-start`` and ``-done`` (fusions and kernel calls
    by their result shape)."""
    kinds = collections.Counter()
    between = collections.defaultdict(list)
    for comp in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                         hlo):
        open_at, lines = {}, comp.split("\n")
        for i, line in enumerate(lines):
            m = _INSTR.match(line)
            if not m:
                continue
            name, shape, op = m.groups()
            base = op[:-6] if op.endswith("-start") else \
                op[:-5] if op.endswith("-done") else op
            if base not in _COLL:
                continue
            if op.endswith("-start"):
                open_at[name] = (i, shape)
            elif op.endswith("-done"):
                src = re.search(r"-done\(%?([\w.\-]+)", line).group(1)
                if src not in open_at:
                    continue
                j, sh = open_at.pop(src)
                inner = []
                for l in lines[j + 1:i]:
                    mm = _INSTR.match(l)
                    if mm and mm.group(3) in ("fusion", "custom-call",
                                              "convolution"):
                        inner.append(mm.group(2))
                scope = re.search(r'op_name="([^"]*)"', lines[j])
                kinds[(base, sh.split("{")[0], "async")] += 1
                between[(base, sh.split("{")[0])].append(
                    (scope.group(1) if scope else "", inner))
            else:
                kinds[(base, shape.split("{")[0], "sync")] += 1
    return {"kinds": kinds, "between": between}


def print_report(rep: dict, tag: str) -> None:
    print(f"--- {tag}: collectives of the scheduled step "
          f"(kind, result, sync/async: count)")
    for (kind, shape, how), n in sorted(rep["kinds"].items()):
        print(f"  {kind:20s} {shape:44s} {how:5s} {n}")
    print(f"--- {tag}: between -start and -done (scope: result shapes of "
          f"the fusions / kernel calls scheduled there)")
    for (kind, shape), sites in sorted(rep["between"].items()):
        empty = sum(1 for _s, inner in sites if not inner)
        print(f"  {kind} {shape}: {len(sites)} pairs, {empty} with nothing "
              f"between")
        for scope, inner in sites[:4]:
            print(f"    {scope[-90:]}: {[s.split('{')[0] for s in inner][:6]}"
                  f"{' ...' if len(inner) > 6 else ''}")


def aot(checkout: str, overrides) -> None:
    """``benchmark/tools/aot.py train`` with the compiled text kept."""
    sys.path.insert(0, checkout)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import deepspeed_tpu
    from benchmark.lib import spec
    from benchmark.runners.train_engine import _ds_config
    from benchmark.tools.aot import _config
    from deepspeed_tpu.parallel import groups

    config = ([a.split("=", 1)[1] for a in overrides
               if a.startswith("config=")] or [CONFIG])[0]
    overrides = [a for a in overrides if not a.startswith("config=")]
    cfg, mix, chips = _config(config, overrides)
    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)[:chips]
    real_devices = jax.devices
    jax.devices = lambda *a, **k: devices          # route as on the chip
    try:
        family = spec.module("families", cfg["family"])
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
        t0 = time.time()
        lowered = engine._jit_fused.lower(*args)
        # a Mosaic call's serialized body carries the checkout's paths
        lowered_text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "",
                              lowered.as_text())
        lowered_sha = hashlib.sha256(lowered_text.encode()).hexdigest()[:16]
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    finally:
        jax.devices = real_devices
    m = compiled.memory_analysis()
    tag = os.path.basename(os.path.abspath(checkout)) or "tree"
    if config != CONFIG:
        tag += "_" + config
    print(f"{tag}: lowered_sha {lowered_sha}; traced and lowered in {t1 - t0:.1f} s, compiled in "
          f"{t2 - t1:.1f} s; per device: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB; tp_overlap_sites "
          f"{getattr(engine, 'tp_overlap_sites', 'n/a')}")
    hlo = compiled.as_text()
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"aot_{tag}.txt"), "w") as f:
        f.write(hlo)
    with open(os.path.join(OUT, f"lowered_{tag}.txt"), "w") as f:
        f.write(lowered_text)
    print_report(schedule_report(hlo), tag)


# ------------------------------------------------------------------ #
# On the chip: the two sublayers alone
# ------------------------------------------------------------------ #
def _device_us(trace_dir: str, names, per: int) -> dict:
    """{name: (median device microseconds an execution / ``per``, how many
    executions)} of the jitted programs ``names`` in the profile under
    ``trace_dir`` (its "XLA Modules" line)."""
    import numpy as np

    from benchmark.lib import xplane_modules

    runs = collections.defaultdict(list)
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for _dev, start, end, prog in xplane_modules.device_modules(path):
            runs[prog].append((end - start) / 1e3)
    out = {}
    for name in names:
        us = [v for prog, vs in runs.items() if name in prog for v in vs]
        out[name] = (float(np.median(us)) / per if us else None, len(us))
    return out


def sublayer(lengths=()) -> None:
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel import groups, tensor_overlap as to

    groups.reset()
    mesh = groups.initialize_mesh(model_parallel_size=2,
                                  data_parallel_size=2).mesh
    B, T, H, F, HQ, HKV = 2, 4096, 4096, 14336, 4096, 1024
    LAYERS = 2
    # the ring itself, whatever the rule says of a chunk this short
    ring = to.Ring(mesh, 2)
    sh = lambda *s: NamedSharding(mesh, P(*s))
    batch = ("dout", "data", "expert")
    norm = lambda x: (x.astype(jnp.float32) * jax.lax.rsqrt(jnp.mean(
        jnp.square(x.astype(jnp.float32)), -1, keepdims=True) + 1e-5)
        ).astype(x.dtype)
    # a stand-in for the attention kernels: per-token, sharded in heads
    mix = lambda q, k, v: q * jnp.tile(jax.nn.sigmoid(k * v), (1, 1, 4))

    def mlp_gspmd(x, ws):
        for wg, wu, wd in ws:
            h = norm(x)
            x = x + jnp.dot(jax.nn.silu(jnp.dot(h, wg)) * jnp.dot(h, wu), wd)
        return x

    def mlp_ring(x, ws, pieces=2):
        to.SCATTER_PIECES = pieces
        x = ring.shard_tokens(x)
        for wg, wu, wd in ws:
            x = x + to.gated_mlp(ring, norm(x), wg, wu, wd, jax.nn.silu)
        return ring.gather_tokens(x)

    def mlp_two_helpers(x, ws):
        to.SCATTER_PIECES = 2
        x = ring.shard_tokens(x)
        for wg, wu, wd in ws:
            g, u = to.gather_column_parallel(
                ring, norm(x), {"gate_proj": wg, "up_proj": wu}).values()
            x = x + to.row_parallel_scatter(ring, jax.nn.silu(g) * u, wd,
                                            name="down_proj")
        return ring.gather_tokens(x)

    def attn_gspmd(x, ws):
        for wq, wk, wv, wo in ws:
            h = norm(x)
            x = x + jnp.dot(mix(jnp.dot(h, wq), jnp.dot(h, wk),
                                jnp.dot(h, wv)), wo)
        return x

    def attn_ring(x, ws, pieces=2):
        to.SCATTER_PIECES = pieces
        x = ring.shard_tokens(x)
        for wq, wk, wv, wo in ws:
            q, k, v = to.gather_column_parallel(
                ring, norm(x), {"q_proj": wq, "k_proj": wk,
                                "v_proj": wv}).values()
            x = x + to.row_parallel_scatter(ring, mix(q, k, v), wo,
                                            name="o_proj")
        return ring.gather_tokens(x)

    col, row = sh(None, "model"), sh("model", None)
    key = iter(jax.random.split(jax.random.key(62), 64))
    w = lambda i, o, s: jax.device_put(
        (jax.random.normal(next(key), (i, o), jnp.float32) * i ** -0.5
         ).astype(jnp.bfloat16), s)
    xs = {t: jax.device_put(jax.random.normal(
        next(key), (B, t, H), jnp.bfloat16), sh(batch))
        for t in (lengths or (T,))}
    mlp_w = [(w(H, F, col), w(H, F, col), w(F, H, row))
             for _ in range(LAYERS)]
    attn_w = [(w(H, HQ, col), w(H, HKV, col), w(H, HKV, col), w(HQ, H, row))
              for _ in range(LAYERS)]
    forms = {
        "mlp_gspmd": (mlp_gspmd, mlp_w), "mlp_ring_p2": (mlp_ring, mlp_w),
        "mlp_ring_p1": (lambda x, ws: mlp_ring(x, ws, 1), mlp_w),
        "mlp_ring_p4": (lambda x, ws: mlp_ring(x, ws, 4), mlp_w),
        "mlp_two_helpers_p2": (mlp_two_helpers, mlp_w),
        "attn_gspmd": (attn_gspmd, attn_w),
        "attn_ring_p2": (attn_ring, attn_w),
        "attn_ring_p1": (lambda x, ws: attn_ring(x, ws, 1), attn_w),
        "attn_ring_p4": (lambda x, ws: attn_ring(x, ws, 4), attn_w),
    }
    if lengths:
        forms = {f"{name}_t{t}": (*forms[name], xs[t]) for t in lengths
                 for name in ("mlp_gspmd", "mlp_ring_p2", "attn_gspmd",
                              "attn_ring_p2")}
    else:
        forms = {name: (*form, xs[T]) for name, form in forms.items()}
    jitted, results = {}, {}
    for name, (f, ws, x) in forms.items():
        def loss(x, ws, f=f):
            return jnp.sum(f(x, ws).astype(jnp.float32) ** 2) * 1e-6
        g = jax.value_and_grad(loss, argnums=(0, 1))
        g.__name__ = name         # the profile's "XLA Modules" event
        jitted[name] = (jax.jit(g), ws, x)
    ref = {}
    for name, (run, ws, x) in jitted.items():
        t0 = time.time()
        out = jax.block_until_ready(run(x, ws))
        kind = name.split("_")[0] + name[name.rfind("_t"):] * bool(lengths)
        flat = [np.asarray(l.astype(jnp.float32))
                for l in jax.tree.leaves(out)]
        if kind not in ref:
            ref[kind] = flat
        err = max(float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))
                  for a, b in zip(flat, ref[kind]))
        results[name] = {"compile_s": time.time() - t0,
                         "max_rel_err_vs_gspmd": err}
    trace_dir = os.path.join(OUT, "sublayer_trace")
    with jax.profiler.trace(trace_dir):
        for name, (run, ws, x) in jitted.items():
            for _ in range(6):
                out = run(x, ws)
            jax.block_until_ready(out)
    for name, (us, n) in _device_us(trace_dir, jitted, LAYERS).items():
        results[name]["device_us_a_layer"] = us
        results[name]["executions"] = n
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "sublayer_sweep.json" if lengths else
                           "sublayer.json"), "w") as f:
        json.dump(results, f, indent=1)
    for name, r in results.items():
        print(name, json.dumps(r))


def permute_rates() -> None:
    """On four chips: what a hop costs alone.  16 MB a rank over ``model``
    (the scatter's traffic at one site) as 1, 2, 4 and 8 ``ppermute``s in
    flight together, beside the all-reduce of 32 MB it replaces, a
    ``psum_scatter`` and an ``all_gather``; ten dependent rounds a program,
    device microseconds a round from the profile's executions."""
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel import groups

    groups.reset()
    mesh = groups.initialize_mesh(model_parallel_size=2,
                                  data_parallel_size=2).mesh
    ROUNDS = 10
    perm = [(0, 1), (1, 0)]

    def rounds(step):
        def body(x):
            for _ in range(ROUNDS):
                x = step(x) * jnp.bfloat16(0.5)
            return x
        return body

    def split_permute(k):
        def step(x):                       # x [1, 2048, 4096] a rank
            parts = jnp.split(x, k, axis=2)
            return jnp.concatenate(
                [lax.ppermute(p, "model", perm) for p in parts], axis=2)
        return step

    forms = {
        "elementwise_only": lambda x: x,
        "permute_1x16MB": split_permute(1),
        "permute_2x8MB": split_permute(2),
        "permute_4x4MB": split_permute(4),
        "permute_8x2MB": split_permute(8),
        "all_reduce_16MB": lambda x: lax.psum(x, "model"),
        "scatter_then_gather_16MB": lambda x: lax.all_gather(
            lax.psum_scatter(x, "model", scatter_dimension=1, tiled=True),
            "model", axis=1, tiled=True),
    }
    x = jax.device_put(
        jnp.ones((2, 4096, 4096), jnp.bfloat16),
        NamedSharding(mesh, P(("dout", "data", "expert"), "model", None)))
    jitted = {}
    for name, step in forms.items():
        f = jax.shard_map(rounds(step), mesh=mesh, axis_names={"model"},
                          in_specs=P(None, "model", None),
                          out_specs=P(None, "model", None), check_vma=False)
        f.__name__ = name
        jitted[name] = jax.jit(f)
        jax.block_until_ready(jitted[name](x))
    trace_dir = os.path.join(OUT, "permute_trace")
    with jax.profiler.trace(trace_dir):
        for name, run in jitted.items():
            for _ in range(5):
                out = run(x)
            jax.block_until_ready(out)
    results = {name: us for name, (us, _n) in
               _device_us(trace_dir, jitted, ROUNDS).items()}
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "permute_rates.json"), "w") as f:
        json.dump(results, f, indent=1)
    print("device us a round (16 MB a rank over model):", json.dumps(results))


def trace_ops(checkout: str, steps: str, out: str) -> None:
    sys.path.insert(0, checkout)
    from benchmark.lib import tracing, xplane_ops
    from benchmark.lib.tracing import TraceView

    found = glob.glob(os.path.join(checkout, "bench_out", CELL, "trace",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    path = max(found, key=os.path.getmtime)
    view = TraceView.from_xplane(path)
    n, nd = float(steps), max(len(view.devices), 1)
    coll = re.compile("|".join(_COLL))
    scopes = {}
    for dev, s, e, op, text in xplane_ops.device_ops(path):
        if op and coll.search(tracing.label_of(text)):
            scopes[(dev, s)] = op.rstrip(":")
    rows = collections.defaultdict(lambda: [0, 0, 0])
    for d in view.devices:
        rest = tracing.union(
            (e.start, e.end) for e in view.device_events
            if e.device == d and not coll.search(e.label))
        for e in view.device_events + view.async_events:
            if e.device != d or not coll.search(e.label):
                continue
            scope = re.sub(r"layers_\d+", "layers_N",
                           scopes.get((d, e.start), ""))
            key = (tracing.op_key(e), scope[-70:])
            span = [(e.start, e.end)]
            rows[key][0] += 1
            rows[key][1] += e.dur
            rows[key][2] += tracing.total(tracing.subtract(span, rest))
    all_s, exposed_s = view.collective_seconds()
    table = sorted(([k[0], k[1], c / nd / n, t / nd / n / 1e6,
                     x / nd / n / 1e6] for k, (c, t, x) in rows.items()),
                   key=lambda r: -r[4])
    result = {"steps": n, "devices": nd,
              "busy_ms_step": view.busy_seconds() * 1e3 / n,
              "collective_ms_step": all_s * 1e3 / n,
              "collective_exposed_ms_step": exposed_s * 1e3 / n,
              "by_op_and_scope": table,
              "top_ops_ms_step": [[k, s * 1e3 / n]
                                  for k, s in view.top_ops(40)]}
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k.endswith("_ms_step") and k != "top_ops_ms_step"}))
    print("collectives: op, scope, count a step a device, ms a step, "
          "exposed ms a step (an operation's own span without the other "
          "operations of its device: overlapping spans counted each)")
    for r in table[:24]:
        print(f"  {r[0][:60]:60s} {r[1]:70s} {r[2]:6.1f} {r[3]:8.3f} "
              f"{r[4]:8.3f}")
    # one layer's forward attention sublayer on the first device, as it ran
    if not view.devices:
        return
    d0 = view.devices[0]
    ops = sorted((s, e, op.rstrip(":"), tracing.label_of(text))
                 for dev, s, e, op, text in xplane_ops.device_ops(path)
                 if dev == d0 and op)
    fwd = [(s, e) for s, e, op, _l in ops
           if "/layers_3/self_attn" in op and "transpose(" not in op]
    if fwd:
        first = min(s for s, _e in fwd)
        lo = min(s for s, _e in fwd if s - first < 3e6)  # one step's
        hi = max(e for s, e in fwd if s - lo < 3e6)
        print(f"layers_3/self_attn forward on device {d0}, one step: "
              f"us from its first operation, us long, operation, scope")
        for s, e, op, label in ops:
            if lo <= s <= hi and e - s >= 2000:
                print(f"  {(s - lo) / 1e3:9.1f} {(e - s) / 1e3:8.1f}  "
                      f"{label[:44]:44s} {op[-60:]}")


if __name__ == "__main__":
    if AOT:
        rest = [a for a in sys.argv[1:] if a != "--aot"]
        tree = [a for a in rest if "=" not in a]
        aot(tree[0] if tree else HERE, [a for a in rest if "=" in a])
    elif "--sublayer" in sys.argv:
        sublayer([int(a) for a in sys.argv[1:] if a.isdigit()])
    elif "--permute" in sys.argv:
        permute_rates()
    elif "--trace-ops" in sys.argv:
        i = sys.argv.index("--trace-ops")
        trace_ops(*sys.argv[i + 1:i + 4])
