"""HLO memory ledger + live occupancy gauges.

Two kinds of memory evidence, one API:

* **compile-time** — :class:`MemoryLedger` records
  ``Compiled.memory_analysis()`` (argument/output/temp/alias bytes) and
  ``cost_analysis()`` (flops, bytes accessed) per named program, with an
  explicit ``{"available": False, "reason": ...}`` record on backends
  that omit the analysis or lowerings that fail — a claim of absence is
  still a record, never a silent skip.  :func:`virtual_mesh_probe` is
  the reusable form of ROADMAP item 3's "HLO memory evidence on virtual
  meshes": it abstract-lowers (``jax.eval_shape`` — **no weights are
  ever materialised**) a ZeRO-3-style sharded train step for a named
  geometry on the host's virtual device mesh and ledgers the result, so
  the 7B ZeRO-3 / MoE / long-seq compile claims are a config entry, not
  a bespoke script.

* **live** — :func:`kv_occupancy` / :func:`tenant_occupancy` /
  :func:`hbm_footprint` read HOST-SIDE bookkeeping only (allocator free
  lists, refcounts, ``seen_tokens``, static geometry arithmetic): wiring
  them into a :class:`~deepspeed_tpu.observability.registry.
  MetricsRegistry` provider adds zero device syncs and zero recompiles
  to the steady-state tick (asserted under TraceGuard in tier-1).

Every gauge name lives in the declared ``observability/*`` namespace
(:mod:`deepspeed_tpu.observability.metrics`), covered by the
``metric-name`` dslint pass.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

#: CompiledMemoryStats fields worth keeping; an attribute a backend
#: does not report is skipped
MEMORY_FIELDS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
    "peak_memory_in_bytes",
    "host_temp_size_in_bytes",
)


def capture_memory_analysis(compiled) -> Dict[str, Any]:
    """``memory_analysis()`` of a compiled program as a plain dict.

    Returns ``{"available": True, <field>: int, ...}`` or
    ``{"available": False, "reason": ...}`` — some backends return None
    or raise; that is evidence too."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # noqa: BLE001 — backend-dependent surface
        return {"available": False, "reason": f"{type(e).__name__}: {e}"}
    if ma is None:
        return {"available": False,
                "reason": "memory_analysis() returned None"}
    out: Dict[str, Any] = {"available": True}
    for f in MEMORY_FIELDS:
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    if len(out) == 1:
        return {"available": False,
                "reason": f"no known fields on {type(ma).__name__}"}
    return out


def capture_cost_analysis(compiled) -> Dict[str, float]:
    """``cost_analysis()`` flops / bytes accessed (0.0 when absent)."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = dict(ca or {})
    except Exception:  # noqa: BLE001
        ca = {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def unavailable_entry(reason: str,
                      meta: Optional[dict] = None) -> Dict[str, Any]:
    """One ledger entry claiming absence — the SINGLE definition of the
    unavailable-record shape every BENCH JSON consumer parses (bench.py,
    bench_serving.py and the subprocess probe build theirs here too)."""
    return {"memory": {"available": False, "reason": str(reason)},
            "cost": {"flops": 0.0, "bytes_accessed": 0.0},
            **({"meta": dict(meta)} if meta else {})}


class MemoryLedger:
    """Named compile-time memory records, exportable as JSON (the BENCH
    record's ``memory_ledger`` key) and as ``observability/hbm_*``
    gauges through a registry provider."""

    def __init__(self):
        self._entries: Dict[str, Dict[str, Any]] = {}

    # -- recording ------------------------------------------------------ #
    def record(self, name: str, compiled,
               meta: Optional[dict] = None) -> Dict[str, Any]:
        entry = {
            "memory": capture_memory_analysis(compiled),
            "cost": capture_cost_analysis(compiled),
            **({"meta": dict(meta)} if meta else {}),
        }
        self._entries[name] = entry
        return entry

    def record_unavailable(self, name: str, reason: str,
                           meta: Optional[dict] = None) -> Dict[str, Any]:
        """An explicit absence record: the program could not be lowered
        or analysed HERE, and the reason travels with the claim."""
        entry = unavailable_entry(reason, meta=meta)
        self._entries[name] = entry
        return entry

    def capture_lowering(self, name: str, fn: Callable, *args,
                         static_argnums=(), meta: Optional[dict] = None,
                         **kwargs) -> Dict[str, Any]:
        """Lower + compile ``fn`` (args may be ShapeDtypeStructs — no
        execution happens) and ledger its analysis; failures become an
        ``unavailable`` record instead of raising."""
        import jax

        try:
            compiled = jax.jit(fn, static_argnums=static_argnums).lower(
                *args, **kwargs).compile()
        except Exception as e:  # noqa: BLE001 — absence is a record
            return self.record_unavailable(
                name, f"{type(e).__name__}: {e}", meta=meta)
        return self.record(name, compiled, meta=meta)

    def merge(self, other: "MemoryLedger") -> None:
        self._entries.update(other._entries)

    # -- reading -------------------------------------------------------- #
    @property
    def entries(self) -> Dict[str, Dict[str, Any]]:
        return dict(self._entries)

    def __len__(self) -> int:
        return len(self._entries)

    def to_json(self) -> Dict[str, Any]:
        return {"schema": "ds-memory-ledger-v1", "entries": self.entries}

    @classmethod
    def from_json(cls, data: Dict[str, Any]) -> "MemoryLedger":
        led = cls()
        if data.get("schema") != "ds-memory-ledger-v1":
            raise ValueError(
                f"not a ds-memory-ledger-v1 payload: {data.get('schema')!r}")
        led._entries = dict(data.get("entries", {}))
        return led

    def telemetry(self) -> Dict[str, float]:
        """``observability/hbm_*`` scalars: per-program HBM byte gauges
        (compile-time constants — reading them costs nothing live)."""
        out: Dict[str, float] = {}
        for name, e in self._entries.items():
            mem = e.get("memory", {})
            if not mem.get("available"):
                out[f"observability/hbm_{name}_unavailable"] = 1.0
                continue
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "peak_memory_in_bytes"):
                if f in mem:
                    short = f.replace("_size_in_bytes", "") \
                        .replace("_in_bytes", "")
                    out[f"observability/hbm_{name}_{short}_bytes"] = \
                        float(mem[f])
        return out


# --------------------------------------------------------------------- #
# Virtual-mesh compile probes (ROADMAP item 3's evidence, as one API)
# --------------------------------------------------------------------- #
def _zero3_shard_spec(shape, mesh_size: int):
    """ZeRO-3-style placement: shard the first divisible dim across the
    data axis, replicate otherwise (what partition padding buys on the
    real engine)."""
    from jax.sharding import PartitionSpec as P

    for i, d in enumerate(shape):
        if d >= mesh_size and d % mesh_size == 0:
            return P(*([None] * i + ["data"]))
    return P()


def zero3_train_lowering(model, batch: int, seq: int,
                         optimizer_dtype="float32"):
    """Abstract-lower a ZeRO-3-style fwd+bwd+Adam train step for
    ``model`` on a virtual ``('data',)`` mesh over ALL visible devices.

    Params, grads, and optimizer moments are sharded per
    :func:`_zero3_shard_spec` (per-device shards; GSPMD materialises the
    gathers), the batch is dp-sharded.  Everything is
    ``ShapeDtypeStruct`` — a 7B lowering runs on a laptop because no
    array is ever allocated.  Returns the lowered object (call
    ``.compile()`` for ``memory_analysis``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))

    def sds(s, dtype=None, spec=None):
        return jax.ShapeDtypeStruct(
            s.shape, dtype or s.dtype,
            sharding=NamedSharding(
                mesh, spec if spec is not None
                else _zero3_shard_spec(s.shape, mesh.size)))

    pshapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 4), jnp.int32))["params"])
    params = jax.tree.map(sds, pshapes)
    moment = jax.tree.map(lambda s: sds(s, jnp.dtype(optimizer_dtype)),
                          pshapes)
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))

    def train_step(params, m, v, ids):
        loss, g = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, ids))(params)
        new_m = jax.tree.map(
            lambda a, b: 0.9 * a + 0.1 * b.astype(a.dtype), m, g)
        new_v = jax.tree.map(
            lambda a, b: 0.999 * a + 0.001 * (b.astype(a.dtype) ** 2),
            v, g)
        new_p = jax.tree.map(
            lambda p, mm, vv: (p.astype(mm.dtype)
                               - 1e-4 * mm / (jnp.sqrt(vv) + 1e-8)
                               ).astype(p.dtype),
            params, new_m, new_v)
        return new_p, new_m, new_v, loss

    return jax.jit(train_step).lower(params, moment, moment, ids)


def _probe_7b_zero3():
    import jax.numpy as jnp

    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.llama2_7b(dtype=jnp.bfloat16)
    return (LlamaForCausalLM(cfg), 8, 1024,
            {"geometry": "llama2-7b 4096h/11008i/32L/32H bf16",
             "zero_stage": 3, "batch": 8, "seq": 1024})


def _probe_125m_zero3():
    import jax.numpy as jnp

    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=2048, dtype=jnp.bfloat16)
    return (LlamaForCausalLM(cfg), 8, 1024,
            {"geometry": "gpt2-125m-class llama 768h/12L bf16",
             "zero_stage": 3, "batch": 8, "seq": 1024})


def _probe_tiny_zero3():
    import jax.numpy as jnp

    from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16)
    return (LlamaForCausalLM(cfg), 8, 32,
            {"geometry": "tiny llama (test probe)", "zero_stage": 3,
             "batch": 8, "seq": 32})


#: named probes: name -> () -> (model, batch, seq, meta).  Extend here
#: for the remaining ROADMAP item 3 configs (Mixtral EP, 64k Ulysses)
#: once their virtual-mesh lowerings exist — the ledger/bench plumbing
#: is already generic.
VIRTUAL_MESH_PROBES: Dict[str, Callable] = {
    "7b_zero3": _probe_7b_zero3,
    "125m_zero3": _probe_125m_zero3,
    "tiny_zero3": _probe_tiny_zero3,
}


def virtual_mesh_probe(name: str,
                       ledger: Optional[MemoryLedger] = None
                       ) -> Dict[str, Any]:
    """Run one named probe in-process and ledger it under
    ``virtual_mesh/<name>``.  Any failure (OOM-sized HLO, missing
    model) becomes an explicit ``unavailable`` record."""
    ledger = ledger if ledger is not None else MemoryLedger()
    key = f"virtual_mesh/{name}"
    builder = VIRTUAL_MESH_PROBES.get(name)
    if builder is None:
        return ledger.record_unavailable(
            key, f"unknown probe {name!r} "
                 f"(have {sorted(VIRTUAL_MESH_PROBES)})")
    import jax

    # outside the try: a backend that fails to start is an error, not an
    # "unavailable" record
    where = {"devices": jax.device_count(),
             "platform": jax.devices()[0].platform}
    try:
        model, batch, seq, meta = builder()
        meta = {**meta, **where}
        lowered = zero3_train_lowering(model, batch, seq)
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — absence is a record
        return ledger.record_unavailable(
            key, f"{type(e).__name__}: {e}")
    return ledger.record(key, compiled, meta=meta)


def virtual_mesh_probe_subprocess(name: str, timeout_s: float = 300.0,
                                  devices: int = 8) -> Dict[str, Any]:
    """Run :func:`virtual_mesh_probe` in a CLEAN subprocess pinned to
    ``devices`` virtual CPU devices (the bench path: the parent may hold
    a TPU backend, and a 7B CPU compile should never wedge the bench —
    on timeout the record says so)."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    code = (
        "import json\n"
        "from deepspeed_tpu.observability.memory import ("
        "MemoryLedger, virtual_mesh_probe)\n"
        f"led = MemoryLedger()\n"
        f"virtual_mesh_probe({name!r}, led)\n"
        "print(json.dumps(led.to_json()))\n")
    try:
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True,
                           timeout=timeout_s,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.dirname(os.path.abspath(__file__)))))
    except subprocess.TimeoutExpired:
        return unavailable_entry(f"probe timed out after {timeout_s}s")
    if r.returncode != 0:
        return unavailable_entry(f"probe rc={r.returncode}: "
                                 f"{r.stderr.strip()[-300:]}")
    try:
        payload = json.loads(r.stdout.strip().splitlines()[-1])
        return MemoryLedger.from_json(payload).entries[
            f"virtual_mesh/{name}"]
    except Exception as e:  # noqa: BLE001
        return unavailable_entry(f"unparseable probe output: {e}")


# --------------------------------------------------------------------- #
# Live occupancy (host-side bookkeeping only — TraceGuard-clean)
# --------------------------------------------------------------------- #
def kv_occupancy(state_manager) -> Dict[str, float]:
    """KV-pool occupancy from allocator/refcount bookkeeping: blocks
    total/free/live, warm (radix-tree-held) and evictable counts, live
    token occupancy, and the derived byte gauges.  Reads NO device
    state."""
    alloc = state_manager.allocator
    kv = state_manager.kv_cache
    total = alloc.num_blocks - 1                     # trash block reserved
    free = alloc.free_blocks
    pc = state_manager.prefix_cache
    evictable = pc.evictable_blocks if pc is not None else 0
    warm = len(alloc._watched)
    live_tokens = sum(s.seen_tokens
                      for s in state_manager._seqs.values())
    # per_token_bytes is dtype-aware (int8 payload + scale records), so
    # the byte gauges stay truthful under KV quantization instead of
    # over-reporting bf16 bytes
    block_bytes = kv.block_size * kv.per_token_bytes
    out = {
        "observability/kv_blocks_total": float(total),
        "observability/kv_blocks_free": float(free),
        "observability/kv_blocks_live": float(total - free),
        "observability/kv_blocks_warm": float(warm),
        "observability/kv_blocks_evictable": float(evictable),
        "observability/kv_tokens_live": float(live_tokens),
        "observability/kv_pool_bytes": float(
            (total + 1) * block_bytes),
        "observability/kv_live_bytes": float(
            (total - free) * block_bytes),
        "observability/kv_sequences_live": float(
            state_manager.n_tracked_sequences),
    }
    tier = getattr(state_manager, "host_tier", None)
    if tier is not None:
        st = tier.stats
        out.update({
            # HBM-resident vs host-restorable capacity, separately
            # gauged: tier entries never inflate kv_blocks_free — a
            # restore consumes real free blocks
            "observability/kv_host_tier_bytes": float(tier.bytes),
            "observability/kv_host_tier_blocks": float(len(tier)),
            "observability/kv_spooled_blocks": float(st.spooled_blocks),
            "observability/kv_restored_blocks": float(st.restored_blocks),
            "observability/kv_tier_dropped_blocks": float(
                st.dropped_blocks),
            "observability/kv_spool_p50_s": st.spool_pct(50),
            "observability/kv_spool_p95_s": st.spool_pct(95),
            "observability/kv_restore_p50_s": st.restore_pct(50),
            "observability/kv_restore_p95_s": st.restore_pct(95),
            # batched tier traffic: blocks moved per gather/scatter
            # dispatch (p50 ~1 means the batching never engages)
            "observability/kv_spool_blocks_per_call_p50":
                st.spool_blocks_pct(50),
            "observability/kv_restore_blocks_per_call_p50":
                st.restore_blocks_pct(50),
        })
    return out


def tree_bytes(tree) -> float:
    """Bytes a pytree of arrays occupies — metadata arithmetic only (no
    transfer; leaves whose dtype numpy cannot size, e.g. PRNG keys, are
    skipped)."""
    import numpy as np

    try:
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
    except Exception:  # pragma: no cover — jax-less analysis contexts
        leaves = []
    total = 0
    for l in leaves:
        if not hasattr(l, "shape"):
            continue
        try:
            total += int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        except TypeError:
            continue
    return float(total)


def hbm_footprint(params, kv_cache=None) -> Dict[str, float]:
    """Static HBM residency arithmetic: weight bytes (+ KV-pool bytes).
    Pure tree-shape arithmetic — no transfers."""
    out = {"observability/hbm_weights_bytes": tree_bytes(params)}
    if kv_cache is not None:
        out["observability/hbm_kv_pool_bytes"] = float(
            kv_cache.num_blocks * kv_cache.block_size
            * kv_cache.per_token_bytes)
    return out


def tenant_occupancy(requests) -> Dict[str, float]:
    """Per-tenant token occupancy over live requests (scheduler queues):
    ``observability/tenant_tokens_<tenant>`` counts each live request's
    full token history.  Host-side list walk, bounded by max_seqs +
    queue depth."""
    out: Dict[str, float] = {}
    for req in requests:
        tenant = getattr(req, "tenant", None) or "default"
        key = f"observability/tenant_tokens_{tenant}"
        out[key] = out.get(key, 0.0) + float(len(req.history))
    return out


def make_occupancy_provider(engine, scheduler=None) -> Callable[
        [], Dict[str, float]]:
    """A registry provider closing over an engine (and optionally its
    scheduler, for tenant occupancy).  The engine's own
    ``occupancy()`` is the canonical gauge set (one body, not two);
    every read is host-side — safe to snapshot between steady-state
    decode ticks (TraceGuard-asserted in tier-1)."""
    def provider() -> Dict[str, float]:
        if hasattr(engine, "occupancy"):
            out = engine.occupancy()
        else:
            out = kv_occupancy(engine.state_manager)
            out.update(hbm_footprint(engine.params))
        if scheduler is not None:
            live = [*scheduler._queued, *scheduler._running.values(),
                    *scheduler._preempted]
            out.update(tenant_occupancy(live))
        return out

    return provider
