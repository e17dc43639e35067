"""Is a program the parent's?  Compiles every serving configuration's
``decode_step`` and two-segment ``put`` programs (the one-token program, one
tile and the full budget) at REAL size for a described v5e from a machine
with no chip (the form of ``benchmark/tools/aot.py``, every family), and for
each program writes

* ``jaxpr_sha`` / ``compiled_sha``: the program's jaxpr (Pallas kernel
  bodies included, source locations stripped) and its compiled text (the
  tables of files, functions and stack frames at its head, each
  instruction's source metadata and the serialized body of each Mosaic call
  cut: all carry paths and line numbers), to say "the parent's to the
  letter" of a program a change moved, or of a cell it bypasses;
* ``pool_relayouts``: the ``copy`` / ``reshape`` / ``transpose`` instructions
  of the COMPILED text whose result has a K/V pool's element count and dtype
  (fused computations included; a ``bitcast`` is free and not counted): each
  is a second pool written in front of a kernel;
* ``moe_relayouts``: the instructions of the compiled text that write
  ``T x k x H`` elements once more, for the program's T rows and the
  configuration's top-k and hidden size: a ``reshape`` or ``convert`` that is
  an instruction of its own, and a ``copy`` wherever it stands (inside a fused
  computation a ``reshape`` or ``convert`` is the fusion's own arithmetic:
  the dispatch's gather ends in one).  Each is the routed rows written again
  between the grouped GEMM and the combine's sum (0 where the configuration
  routes to no more than one expert);
* ``temp_gb``: the temporaries of XLA's memory analysis.

    JAX_PLATFORMS=cpu python3 tools/program_text.py <checkout> <out.json> [dump=<dir>] [config ...]

``dump=<dir>`` also writes each program's two hashed texts there, to ``diff``
where a hash differs.

Run it on ``git archive`` of the parent and on the change (``<checkout>`` is
what it imports ``deepspeed_tpu`` and ``benchmark`` from) and compare the two
files.  No chip, no value, no time.  (PRs 27, 29, 30, 40 and 41 each kept a
copy of this under ``tools/chip_calls/``; their ``*_results/`` stay
there.)"""
import hashlib
import json
import os
import re
import sys
import time

root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
only = [a for a in sys.argv[3:] if "=" not in a]
dump = [a.split("=", 1)[1] for a in sys.argv[3:] if a.startswith("dump=")]
sys.path.insert(0, root)
os.environ["JAX_PLATFORMS"] = "cpu"
from benchmark.tools import aot  # noqa: E402,F401  (sets the TPU env)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402
from jax.experimental import topologies                     # noqa: E402
from jax.sharding import SingleDeviceSharding               # noqa: E402

from benchmark.lib import spec                              # noqa: E402
from deepspeed_tpu.inference.v2 import (                    # noqa: E402
    InferenceEngineV2, RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (  # noqa: E402
    packed_length)

WRITE = re.compile(
    r"= (\w+)\[([0-9,]+)\]\S* (copy|reshape|transpose|convert)\(")


def _writes(text: str):
    """``(op, dtype, shape, elements, fused)`` of every ``copy`` /
    ``reshape`` / ``transpose`` / ``convert`` of ``text``; ``fused``: the
    instruction stands inside a fused computation."""
    fused = False
    for line in text.splitlines():
        if line.startswith(("%fused_computation", "fused_computation")):
            fused = True
        elif line.startswith("}"):
            fused = False
        m = WRITE.search(line)
        if m:
            yield (m.group(3), m.group(1), m.group(2), int(np.prod(
                [int(n) for n in m.group(2).split(",")])), fused)


def relayouts(text: str, pools: set) -> list:
    """The instructions of ``text`` that write an array as large as a pool
    in another layout: ``(op, dtype[shape])`` each."""
    return [f"{op} {dtype}[{shape}]"
            for op, dtype, shape, n, _ in _writes(text)
            if op != "convert" and (dtype, n) in pools]


def moe_relayouts(text: str, elements: int) -> list:
    """The instructions of ``text`` that write ``elements`` values once more
    in another layout or dtype: ``(op, dtype[shape])`` each."""
    return [f"{op} {dtype}[{shape}]"
            for op, dtype, shape, n, fused in _writes(text)
            if n == elements and op != "transpose"
            and (op == "copy" or not fused)]


def without_sources(text: str) -> str:
    """Compiled HLO text with what names source files and lines cut."""
    lines = text.splitlines()
    if "FileNames" in lines:
        head = lines.index("FileNames")
        body = next(i for i in range(head, len(lines))
                    if lines[i].startswith(("%", "ENTRY")))
        lines[head:body] = []
    text = re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))
    return re.sub(r'("body":")[^"]*', r"\1(cut)", text)


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


bench = spec.benchmark_spec()
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
results = {}
for entry in bench["configs"]:
    cfg = spec.load_json(os.path.join(root, entry["file"]))
    if "serve" not in cfg or (only and entry["name"] not in only):
        continue
    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    bs = int(sv["block_size"])
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=one),
        family.serve_param_shapes(cfg))
    eng = InferenceEngineV2(
        family.serve_model(cfg, bs), params,
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {
                "max_ragged_batch_size": sv["token_budget"],
                "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
                "max_context": sv["max_context"]},
            "kv_cache": {"block_size": bs, "num_blocks": 4}}))
    kv = eng.state_manager.kv_cache
    # (a layer keeps ``kv.passes`` caches in one pool: kv_cache.py)
    rows = getattr(kv, "passes", 1) * int(sv["kv_pool_blocks"]) * bs
    pooled = {f"layer_{i}" for i in kv.kv_layers}
    window = {f"layer_{i}" for i in kv.window_layers}
    # the global group's pools at the cell's size; the window group's as the
    # state manager sized them; state slots as they are
    cache = {
        name: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                ((rows,) if name in pooled - window else a.shape[:1])
                + a.shape[1:], a.dtype, sharding=one), leaves)
        for name, leaves in kv.cache.items()}
    pools = {(np.dtype(l.dtype).name.replace("bfloat16", "bf16")
              .replace("float32", "f32").replace("int8", "s8"),
              int(np.prod(l.shape)))
             for name in pooled for l in jax.tree.leaves(cache[name])}
    S = int(sv["max_ragged_sequence_count"])
    B = -(-int(sv["max_context"]) // bs)
    # a token's routed rows: its top-k choices x the hidden size
    topk = int(cfg.get("num_experts_per_tok", cfg.get("moe_topk", 1)))
    routed = topk * int(cfg["hidden_size"]) if topk > 1 else 0
    grouped = getattr(eng, "_grouped", False)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    extra = ((ints(S),) if eng._stateful else ()) \
        + ((ints(S, B),) if grouped else ())
    progs = {"decode_step": (eng._get_decode_step(), S,
                             (ints(S, B), ints(S), ints(S)) + extra)}
    for tokens in (0, 128, int(sv["token_budget"])):
        fn = eng._get_step(S + tokens, eng.PREFILL_TILE)
        progs[fn.__name__] = (fn, S + tokens, (ints(packed_length(
            S + tokens, S, B, eng._stateful,
            **({"win": True} if grouped else {}))),))
    real_devices = jax.devices
    jax.devices = lambda *a, **k: list(topo.devices)[:1]   # route as the chip
    try:
        for pname, (fn, rows_in, args) in progs.items():
            t0 = time.time()
            traced = fn.trace(params, cache, *args)
            jaxpr = re.sub(r" at [^ \n]*\.py:\d+", "", str(traced.jaxpr))
            jaxpr = re.sub(re.escape(root) + "/", "", jaxpr)
            compiled = traced.lower().compile()
            text = compiled.as_text()
            found = relayouts(text, pools)
            routed_found = moe_relayouts(text, rows_in * routed) \
                if routed else []
            plain = without_sources(text)
            for kind, txt in (("jaxpr", jaxpr), ("compiled", plain)) \
                    if dump else ():
                os.makedirs(dump[0], exist_ok=True)
                with open(os.path.join(
                        dump[0], f"{entry['name']}.{pname}.{kind}.txt"),
                        "w") as f:
                    f.write(txt)
            results[f"{entry['name']}/{pname}"] = {
                "pools": sorted(f"{d}x{n}" for d, n in pools),
                "pool_relayouts": len(found),
                "relayouts": sorted(set(found)),
                "moe_relayouts": len(routed_found),
                "moe_relayout_ops": sorted(set(routed_found)),
                "temp_gb": round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e9, 3),
                "jaxpr_sha": sha(jaxpr), "compiled_sha": sha(plain),
                "compile_s": round(time.time() - t0)}
            print(entry["name"], pname, json.dumps(
                results[f"{entry['name']}/{pname}"]), flush=True)
    finally:
        jax.devices = real_devices
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
