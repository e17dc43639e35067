"""HLO memory ledger + live occupancy gauges.

Two kinds of memory evidence, one API:

* **compile-time** — :class:`MemoryLedger` records
  ``Compiled.memory_analysis()`` (argument/output/temp/alias bytes) and
  ``cost_analysis()`` (flops, bytes accessed) per named program, with an
  explicit ``{"available": False, "reason": ...}`` record on backends
  that omit the analysis or lowerings that fail — a claim of absence is
  still a record, never a silent skip.

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
    """One ledger entry claiming absence — the single definition of the
    unavailable-record shape."""
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
    win = getattr(state_manager, "win_allocator", None)
    if win is not None:
        # a model with window and global KV layers (kv_groups): the gauges
        # above are the global group's; the window group's pool beside them
        win_live = win.num_blocks - 1 - win.free_blocks
        out.update({
            "observability/kv_window_blocks_total": float(win.num_blocks - 1),
            "observability/kv_window_blocks_live": float(win_live),
            "observability/kv_window_pool_bytes": float(
                kv.window_pool_bytes),
            # at the window group's OWN row (a row a group): what its live
            # blocks hold, as kv_live_bytes is the global group's
            "observability/kv_window_live_bytes": float(
                win_live * kv.block_size * kv.window_token_bytes),
        })
    pool = getattr(state_manager, "state_pool", None)
    if pool is not None:
        # recurrent state: bytes a SEQUENCE holds whatever its length,
        # told apart from the pool's bytes a token
        out.update({
            "observability/state_slots_total": float(pool.num_slots),
            "observability/state_slots_held": float(pool.held),
            "observability/state_pool_bytes": float(pool.total_bytes),
            "observability/state_live_bytes": float(pool.held_bytes),
        })
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
