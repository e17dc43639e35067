"""PR 56, one-off for the chip: which LAYOUT of a Gated DeltaNet state of 30 heads x (96 keys, 192 values)
do the two delta-rule kernels run fastest at, at the Olmo-Hybrid cell's shapes (a decode step of 128 one-token
rows over a pool of 129 slots; a chunk call of 1,024 rows in 8 tiles of 128)?

Every form goes through THE kernels of ``ops/gated_delta_rule.py`` (``_gdn_step_call`` / ``_gdn_chunk_call``
with the heads a grid step holds given by hand); a layout is a reshape or a zero padding of the operands, which
is exact (a dead head has ``g = 0``, ``beta = 0``; a zero key row or value lane stays zero):

* ``natural-hb<n>``: ``[30, 96, 192]`` as the mathematics has it; the chip tiles a float32 array ``(8, 128)``,
  so each row of the pool holds 256 lanes in HBM (a third more bytes than the 2,211,840 B a layer a sequence);
* ``dead32-hb<n>``: two dead heads, ``[32, 96, 192]`` (6.7% more bytes on top of the lanes' third);
* ``dk128-hb<n>``: keys zero-padded to 128, ``[30, 128, 192]`` (a third more rows on top of the lanes' third);
* ``dv256-hb<n>``: values zero-padded to 256, ``[30, 96, 256]``: what the chip holds anyway, said out loud;
* ``pairs-hb<n>``: two heads side by side on the lanes, ``[15, 96, 384]``, three whole lane tiles and no
  padding.  Since PR 58 this is the PROGRAM's layout (``gdr.state_leaf_shape``) and both kernels take it by the
  pool's shape, so the form goes through ``_gdn_step_call`` / ``_gdn_chunk_call`` like every other (``hb``
  counts pairs); the sketch PR 56 timed it with (``_pair_step_kernel``, step only) is gone, the kernel it
  sketched being ``_gdn_step_kernel``'s ``pair`` form.

``step`` forms print microseconds a call at 128 rows (six donated pools, one a layer as the model has) beside
the least time for the mathematics' bytes (``benchmark/lib/costs_gdn.py``: each slot read and written once);
``chunk`` forms the same at 1,024 rows.  ``--rehearse`` runs every named form once at a small shape of the same
class in interpret mode against the composition (no chip); ``--aot`` compiles every named form for a described
v5e at the cell's shape (no chip).

    python tools/chip_calls/pr56_candidates.py [--aot|--rehearse] step:natural-hb6 chunk:natural-hb3 ...
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.ops import gated_delta_rule as gdr  # noqa: E402

F32 = jnp.float32
H, DK, DV = 30, 96, 192


def _padded(layout, pool, q, k, v, g, beta):
    """The operands of a layout that is a zero padding of the natural one, and how to cut a result back."""
    h, dk, dv = pool.shape[1:]
    ph, pk, pv = {"natural": (0, 0, 0), "dead32": (2, 0, 0), "dk128": (0, 128 - dk, 0),
                  "dv256": (0, 0, 256 - dv)}[layout]
    pad = lambda x, *w: jnp.pad(x, tuple((0, n) for n in w))
    ops = (pad(pool, 0, ph, pk, pv), pad(q, 0, ph, pk), pad(k, 0, ph, pk), pad(v, 0, ph, pv), pad(g, 0, ph),
           pad(beta, 0, ph))
    return ops, lambda o, p: (o[:, :h, :dv], p[:, :h, :dk, :dv])


def form(name):
    """``<kernel>:<layout>-hb<n>`` -> (kernel, prepare(pool, rows...) -> (operands, cut), call)."""
    kern, rest = name.split(":")
    layout, hb = rest.rsplit("-hb", 1)
    hb = int(hb)
    if layout == "pairs":
        prep = lambda pool, q, k, v, g, beta: (
            (gdr._pairs(pool), q, k, v, g, beta), lambda o, p: (o, gdr._unpairs(p)))
    else:
        prep = functools.partial(_padded, layout)
    if kern == "step":
        return kern, prep, lambda interpret: lambda *a: gdr._gdn_step_call(*a, hb=hb, interpret=interpret)
    return kern, prep, lambda interpret: lambda *a, tile: gdr._gdn_chunk_call(
        *a, tile=tile, chunk=min(gdr.CHUNK, tile), hb=hb, interpret=interpret)


def inputs(kern, h, dk, dv, slots, rows, tile, seed=23):
    ks = jax.random.split(jax.random.fold_in(jax.random.key(0), seed), 6)
    unit = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (rows, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (rows, h, dk)) + 0.3)
    v = jax.random.normal(ks[2], (rows, h, dv))
    g = -0.05 * jnp.abs(jax.random.normal(ks[3], (rows, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, h)))
    pool = jax.random.normal(ks[5], (slots + 1, h, dk, dv))
    if kern == "step":
        where = (jax.random.permutation(ks[5], slots)[:rows].astype(jnp.int32), jnp.arange(rows) % 7 == 0)
    else:
        nt = rows // tile
        where = (jnp.asarray(([1] * 3 + [3] * 3 + [0, slots] * nt)[:nt], jnp.int32),
                 jnp.asarray(([1, 0, 0] + [0] * nt)[:nt], bool))
    return pool, (q, k, v, g, beta), where


def rehearse(names):
    for name in names:
        kern, prep, call = form(name)
        hb = int(name.rsplit("-hb", 1)[1])
        h = 2 * 3 * hb if "dead32" not in name else 30
        pool, rows, where = inputs(kern, h, 24, 64 if "pairs" in name else 48, 8, 8 if kern == "step" else 256, 32)
        if "dk128" in name or "dv256" in name or "dead32" in name:
            pool, rows, where = inputs(kern, 30, DK, DV, 8, 8 if kern == "step" else 256, 32)
        ops, cut = prep(pool, *rows)
        kw = {} if kern == "step" else {"tile": 32}
        got = cut(*call(True)(*ops, *where, **kw))
        ref = gdr.gdn_step_reference if kern == "step" else functools.partial(gdr.gdn_chunk_reference, tile=32)
        want = ref(pool, *rows, *where)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
        print(json.dumps({"form": name, "rehearsed_err": err, "ok": err < 1e-4}), flush=True)


def aot(names):
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    for name in names:
        kern, prep, call = form(name)
        pool, rows, where = jax.eval_shape(lambda: inputs(kern, H, DK, DV, 128, 128 if kern == "step" else 1024, 128))
        ops, _ = jax.eval_shape(lambda p, *r: prep(p, *r)[0], pool, *rows), None
        sds = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
        kw = {} if kern == "step" else {"tile": 128}
        t0 = time.time()
        try:
            c = jax.jit(functools.partial(call(False), **kw)).lower(*sds(ops), *sds(where)).compile()
            m = c.memory_analysis()
            print(json.dumps({"form": name, "compiled_s": round(time.time() - t0, 1),
                              "args_mb": round(m.argument_size_in_bytes / 1e6, 1),
                              "temp_mb": round(m.temp_size_in_bytes / 1e6, 1)}), flush=True)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is the finding
            print(json.dumps({"form": name, "refused": str(e)[:400]}), flush=True)


def timed(names, layers=6, repeats=10):
    sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
    out = []
    for name in names:
        kern, prep, call = form(name)
        n_rows = 128 if kern == "step" else 1024
        pool, rows, where = inputs(kern, H, DK, DV, 128, n_rows, 128)
        ops, cut = prep(pool, *rows)
        kw = {} if kern == "step" else {"tile": 128}
        fn = functools.partial(call(False), **kw)
        try:
            got = cut(*fn(*ops, *where))
            ref = gdr.gdn_step_reference if kern == "step" else functools.partial(gdr.gdn_chunk_reference, tile=128)
            want = ref(pool, *rows, *where)
            live = slice(0, n_rows) if kern == "step" else slice(0, 7 * 128)
            scale = max(float(jnp.max(jnp.abs(w))) for w in want)
            err = max(float(jnp.max(jnp.abs(got[0][live] - want[0][live]))),
                      float(jnp.max(jnp.abs(got[1][:128] - want[1][:128])))) / scale

            def stacked(pools, *rest):
                y, new = 0.0, []
                for p in pools:
                    o, p = fn(p, *rest)
                    y, new = y + o, new + [p]
                return y, new

            run = jax.jit(stacked, donate_argnums=0)
            pools = [ops[0] + 0.0 for _ in range(layers)]
            y, pools = run(pools, *ops[1:], *where)
            y.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(repeats):
                y, pools = run(pools, *ops[1:], *where)
            y.block_until_ready()
            us = (time.perf_counter() - t0) / repeats / layers * 1e6
            seqs = n_rows if kern == "step" else 3
            moved = (seqs * 2 * H * DK * DV + n_rows * H * (2 * DK + 2 * DV + 2)) * 4
            least = max(n_rows * H * (7 * DK * DV + 2 * DV) / 197e12, moved / 819e9) * 1e6
            rec = {"form": name, "max_err": round(err, 7), "us_per_call": round(us, 1),
                   "least_us": round(least, 1), "least_share_pct": round(100 * least / us, 2)}
        except Exception as e:  # noqa: BLE001
            rec = {"form": name, "failed": str(e)[:300]}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_ROOT, "chiprun_out", "pr56_candidates.jsonl"), "a") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    if "--rehearse" in sys.argv:
        rehearse(args)
    elif "--aot" in sys.argv:
        aot(args)
    else:
        timed(args)
