"""PR 60: the MoE combine alone (the unsort gather and the weighted sum of a
token's k rows, ``ops/grouped_gemm.py::grouped_moe_ffn``'s last lines) in
three forms at the routed-expert cells' shapes:

* ``parent``: the gather token-major, ``[T, k, H]`` in float32, summed over
  axis 1 (the form before PR 60);
* ``slabs``: ONE gather choice-major, ``down[dest.reshape(T, k).T]`` =
  ``[k, T, H]``, the k terms written out (the form of PR 60);
* ``gathers``: k gathers of ``[T, H]``, the k terms written out.

    python3 tools/chip_calls/pr60_combine_forms.py            # on the chip: microseconds a layer, by the host's clock and by the profile's executions
    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr60_combine_forms.py --aot       # no chip: XLA's temporaries and cycles for a described v5e
    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr60_combine_forms.py --rehearse  # no chip: tiny shapes, values only

A timed call is one jitted program over ``LAYERS`` buffers (a layer each, as
a step program holds them).  ``us_a_layer`` is the host's clock over 20
calls in flight (a launch costs ~0.5 ms here, so nothing under 130 us a
layer is told apart by it); ``device_us_a_layer`` is the median execution
of the program on the profile's "XLA Modules" line.  Every form's result is
compared with ``parent``'s.
"""
import json
import os
import re
import sys
import time

import numpy as np

AOT, REHEARSE = "--aot" in sys.argv, "--rehearse" in sys.argv
if AOT:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

#: (cell's program, T rows, top-k, hidden size)
SHAPES = [
    ("granite_T1152", 1152, 10, 4096), ("granite_decode", 128, 10, 4096),
    ("qwen3next_T1056", 1056, 10, 2048), ("qwen3next_T544", 544, 10, 2048),
    ("qwen3next_decode", 32, 10, 2048),
    ("olmoe_T1056", 1056, 8, 2048), ("olmoe_decode", 32, 8, 2048),
    ("moonlight_T1088", 1088, 6, 2048), ("moonlight_decode", 64, 6, 2048),
    ("lfm2_T1152", 1152, 4, 2048), ("lfm2_decode", 128, 4, 2048),
    ("trinity_T1056", 1056, 4, 3072), ("trinity_decode", 32, 4, 3072),
    ("longcat_T1088", 1088, 12, 6144), ("longcat_decode", 64, 12, 6144),
    ("glm5_T1040", 1040, 8, 6144), ("glm5_decode", 16, 8, 6144),
    ("rows_40", 40, 10, 2048), ("rows_132", 132, 10, 2048),
]
LAYERS = 4


def _weigh(rows, topw, dtype):
    w = topw.astype(jnp.float32)
    acc = rows(0).astype(jnp.float32) * w[:, 0, None]
    for j in range(1, topw.shape[1]):
        acc = acc + rows(j).astype(jnp.float32) * w[:, j, None]
    return acc.astype(dtype)


def parent(down, dest, topw):
    t, k = topw.shape
    back = down[dest].astype(jnp.float32).reshape(t, k, down.shape[1])
    return jnp.sum(back * topw.astype(jnp.float32)[..., None],
                   axis=1).astype(down.dtype)


def slabs(down, dest, topw):
    rows = down[dest.reshape(topw.shape).T]
    return _weigh(lambda j: rows[j], topw, down.dtype)


def gathers(down, dest, topw):
    by_choice = dest.reshape(topw.shape).T
    return _weigh(lambda j: down[by_choice[j]], topw, down.dtype)


FORMS = {"parent": parent, "slabs": slabs, "gathers": gathers}


def layers_of(form, name):
    def run(downs, dest, topw):
        return [form(d, dest, topw) for d in downs]
    run.__name__ = name           # the profile's "XLA Modules" event
    return jax.jit(run)


def device_us(trace_dir):
    """{program: median device microseconds an execution} of the profile
    under ``trace_dir`` (``benchmark/lib/xplane_modules.py``)."""
    import glob
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.lib import xplane_modules
    runs = {}
    for path in glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        for _dev, start, end, prog in xplane_modules.device_modules(path):
            runs.setdefault(prog, []).append((end - start) / 1e3)
    return {prog: float(np.median(us)) for prog, us in runs.items()}


def aot():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    out = {}
    for name, t, k, h in SHAPES:
        m = -(-t * k // 128) * 128
        args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in (
            ((m, h), jnp.bfloat16), ((t * k,), jnp.int32),
            ((t, k), jnp.float32))]
        for fname, form in FORMS.items():
            c = jax.jit(form).lower(*args).compile()
            text = c.as_text()
            entry = text[text.index("ENTRY"):]
            out[f"{name}/{fname}"] = {
                "temp_mb": round(
                    c.memory_analysis().temp_size_in_bytes / 1e6, 1),
                "estimated_cycles": sum(int(x) for x in re.findall(
                    r'"estimated_cycles":"?(\d+)', text)),
                # what the entry computation writes of the routed rows' size
                "entry_relayouts": [
                    f"{op} {dtype}[{dims}]" for dtype, dims, op in re.findall(
                        r"= (\w+)\[([0-9,]+)\]\S* (reshape|copy|convert)\(",
                        entry)
                    if np.prod([int(n) for n in dims.split(",")])
                    >= t * k * h]}
            print(name, fname, json.dumps(out[f"{name}/{fname}"]), flush=True)
    return out


def timed():
    shapes = [(n, 24, k, 256) for n, _, k, _ in SHAPES[:6]] if REHEARSE \
        else SHAPES
    out = {"device": str(jax.devices()[0].device_kind)}
    trace_dir = os.path.join(os.environ.get("TMPDIR", "/tmp"), "pr60_forms")
    jax.profiler.start_trace(trace_dir)
    for name, t, k, h in shapes:
        rng = np.random.default_rng(t * k)
        m = -(-t * k // 128) * 128
        downs = [jnp.asarray(rng.standard_normal((m, h)), jnp.bfloat16)
                 for _ in range(LAYERS)]
        dest = jnp.asarray(rng.permutation(t * k), jnp.int32)
        topw = jnp.asarray(rng.random((t, k)), jnp.float32)
        want = None
        for fname, form in FORMS.items():
            fn = layers_of(form, f"{name}_{fname}")
            got = jax.block_until_ready(fn(downs, dest, topw))
            got0 = np.asarray(got[0], np.float32)
            want = got0 if want is None else want
            err = float(np.abs(got0 - want).max() / np.abs(want).max())
            n = 3 if REHEARSE else 20
            best = []
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(n):
                    got = fn(downs, dest, topw)
                jax.block_until_ready(got)
                best.append((time.perf_counter() - t0) / n / LAYERS * 1e6)
            least = (t * k * h * 2 + t * h * 2) / 819e9 * 1e6
            out[f"{name}/{fname}"] = {
                "us_a_layer": round(min(best), 1),
                "least_us": round(least, 1), "err_vs_parent": err}
            print(name, fname, json.dumps(out[f"{name}/{fname}"]), flush=True)
    jax.profiler.stop_trace()
    for prog, us in device_us(trace_dir).items():
        key = prog[::-1].replace("_", "/", 1)[::-1]
        if key in out:
            out[key]["device_us_a_layer"] = round(us / LAYERS, 1)
    for key, row in out.items():
        print(key, json.dumps(row), flush=True)
    return out


if __name__ == "__main__":
    result = aot() if AOT else timed()
    if not REHEARSE:
        os.makedirs("chiprun_out/p60", exist_ok=True)
        with open("chiprun_out/p60/combine_forms_%s.json"
                  % ("aot" if AOT else "chip"), "w") as f:
            json.dump(result, f, indent=1)
