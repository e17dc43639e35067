"""``observability/*`` metric declarations.

The observability layer emits its own telemetry: tracer ring health
(span counts, overwritten records), compile-time HBM gauges from the
:class:`~deepspeed_tpu.observability.memory.MemoryLedger`, and the live
KV/tenant occupancy gauges.  Declaring the names here (same pattern as
``serving``/``fleet``/``resilience``) puts them under the
``metric-name`` dslint pass and the registry's unknown-name runtime
check.
"""

from __future__ import annotations

from deepspeed_tpu.observability.registry import MetricsRegistry


def _declare(reg: MetricsRegistry) -> None:
    # tracer ring health (satellite: silent ring-wrap made visible)
    reg.counter("observability/dropped_spans",
                help="tracer ring records overwritten before export")
    reg.counter("observability/spans_recorded",
                help="total span/instant records ever written")
    reg.gauge("observability/spans_open",
              help="currently open (unfinished) spans")
    # what the process built (the ``setup/build_program`` records of the
    # process tracer, summed by the one jax.monitoring listener) and how
    # long an engine took to be ready; exported by the engines' providers
    reg.counter("observability/programs_built",
                help="executables JAX built in this process (compiled or "
                     "read from the persistent cache)")
    reg.counter("observability/program_build_seconds", unit="s",
                help="seconds of tracing, lowering and backend compile "
                     "(or cache read) over those builds")
    reg.counter("observability/program_cache_misses",
                help="builds the persistent compile cache was asked for "
                     "and did not hold")
    reg.gauge("observability/time_to_first_launch_s", unit="s",
              help="the package's import -> the engine's first step "
                   "program built and launched")
    # compile-time HBM ledger gauges + static residency arithmetic
    reg.gauge("observability/hbm_*", unit="bytes",
              help="HLO memory ledger / static HBM residency gauges")
    # live KV-pool occupancy (host-side bookkeeping reads only)
    reg.gauge("observability/kv_*",
              help="KV pool occupancy: blocks live/warm/evictable, "
                   "token + byte gauges")
    # recurrent-state slots beside the KV pool (a model with
    # linear-attention layers): bytes a SEQUENCE holds, whatever its length
    reg.gauge("observability/state_*",
              help="recurrent-state slot pool: slots total/held, pool and "
                   "live bytes")
    # host cold-tier gauges (kv_cache.host_tier): spooled/restored block
    # counters, tier residency, and the spool/restore latency
    # percentiles the session-mix bench reports — declared exactly (on
    # top of the kv_* family) so the tier surface is self-documenting
    reg.gauge("observability/kv_host_tier_bytes", unit="bytes",
              help="bytes of KV spooled to the host cold tier")
    reg.gauge("observability/kv_host_tier_blocks",
              help="blocks currently resident in the host cold tier")
    reg.counter("observability/kv_spooled_blocks",
                help="blocks ever demoted HBM -> host tier")
    reg.counter("observability/kv_restored_blocks",
                help="blocks restored host tier -> HBM on attach/resume")
    reg.counter("observability/kv_tier_dropped_blocks",
                help="tier entries dropped past the host byte budget")
    reg.gauge("observability/kv_spool_p50_s", unit="s",
              help="spool (gather->host) latency p50 over a bounded "
                   "window")
    reg.gauge("observability/kv_spool_p95_s", unit="s",
              help="spool latency p95")
    reg.gauge("observability/kv_restore_p50_s", unit="s",
              help="restore (host->scatter) latency p50, transfer "
                   "blocked — not dispatch")
    reg.gauge("observability/kv_restore_p95_s", unit="s",
              help="restore latency p95")
    reg.gauge("observability/kv_spool_blocks_per_call_p50",
              help="blocks demoted per batched gather dispatch (p50)")
    reg.gauge("observability/kv_restore_blocks_per_call_p50",
              help="blocks restored per batched scatter dispatch (p50)")
    # per-tenant token occupancy over live requests
    reg.gauge("observability/tenant_tokens_*", unit="tokens",
              help="live token occupancy per tenant")
    # optimizer-offload transfer streams (runtime/zero/offload.py
    # OffloadTransferStats.snapshot(), exported through the engine's
    # register_observability provider) — the pipelined host-Adam path's
    # spill/restore accounting and its structural overlap evidence
    reg.counter("observability/offload_spilled_bytes", unit="bytes",
                help="master/opt bytes streamed device -> host tier")
    reg.counter("observability/offload_restored_bytes", unit="bytes",
                help="master/opt bytes streamed host tier -> device")
    reg.counter("observability/offload_transfers",
                help="bucket transfer dispatches (spills + restores)")
    reg.counter("observability/offload_pipeline_steps",
                help="optimizer steps taken through the pipelined "
                     "per-bucket offload path")
    reg.gauge("observability/offload_buckets",
              help="transfer buckets per pipelined step (byte-balanced "
                   "over offloaded leaves)")
    reg.gauge("observability/offload_overlap_fraction",
              help="fraction of bucket transfers dispatched while "
                   "another bucket's update was still in flight")
    reg.gauge("observability/offload_bucket_transfer_p50_s", unit="s",
              help="bucket transfer latency p50 (profile_transfers "
                   "mode only — blocked, not dispatch)")
    reg.gauge("observability/offload_bucket_transfer_p95_s", unit="s",
              help="bucket transfer latency p95 (profile_transfers "
                   "mode only)")


_declare(MetricsRegistry.default())
