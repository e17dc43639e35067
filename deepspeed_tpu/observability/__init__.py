"""Observability layer: request-scoped distributed tracing, the unified
metrics registry, the crash flight recorder (the capture surface, PR 12)
— and the HLO memory ledger + live occupancy gauges over those captures
(:mod:`~deepspeed_tpu.observability.memory`).

Typical use::

    from deepspeed_tpu.observability import Tracer, write_chrome_trace

    tracer = Tracer(tid="replica0")
    sched = ContinuousBatchScheduler(engine, tracer=tracer)
    ...drive traffic...
    write_chrome_trace("trace.json", tracer.export_events())
    # -> load in https://ui.perfetto.dev

Inside a tick the scheduler and the engine it drives record one span
tree on that tracer (the scheduler hands it over): ``tick`` → ``pack`` /
``prefill`` / ``decode`` / ``verify`` / ``sample`` → ``engine/build_batch``,
``engine/ragged_step``, ``engine/fetch_logits``, ``engine/decode_prep``,
``engine/decode_step``, ``engine/verify_step``, ``fetch``, ``advance``.  A
counter is recorded once, on the span that owns it: the ``tick`` span
closes with ``kind`` and ``emitted``, ``engine/build_batch`` with the
``tokens`` it fed and the ``bucket`` they were padded to, a dispatch span
with the ``launch`` number and the ``program`` name, the ``fetch`` /
``engine/fetch_logits`` that waits for that launch with its ``launch``, and
a ``put`` tick's ``sample`` with ``sampled`` / ``device_sampled`` (tokens
emitted, and how many were the step program's argmax).  The catalogue (name, site, parent, attrs), the
one rule for when a span is also a ``jax.profiler.TraceAnnotation``
(opened with ``Tracer.span`` while ``enable_device_annotations`` is on)
and the off-path cost (one attribute test, the shared null context) are
in :mod:`~deepspeed_tpu.observability.tracer`.  ``annotate()`` /
``step_annotation()`` remain for callers that have no tracer.

What happens once a process is on a tracer of its own,
``process_tracer()`` (made on first use, always on): ``setup/import`` (the
package's import, closing with ``process_start_ns``), ``setup/engine_init``
and ``setup/init_parameters`` (the engines' constructors and the sharded
parameter init), and ONE ``setup/build_program`` record an executable JAX
builds anywhere in the process, under the program's own name with its
``trace_s`` / ``lower_s`` / ``backend_s`` and what the persistent cache said
(``cache``: hit / miss / none), folded from ``jax.monitoring``'s events by
the program's one listener.  One record a build: every tracer reads one
clock, so the tick that recompiled is the span of the scheduler's tracer
whose interval holds the record's end, and ``merge_events(process_tracer().
export_events(), tracer.export_events())`` is one timeline.

Every request carries a ``trace_id`` minted at submit; spans from every
replica incarnation it touches (kill→replay, rolling restarts,
disaggregated prefill→decode handoff) share that id, so the exported
timeline shows ONE request's whole life.  ``tools/obs_dump.py`` renders
and schema-validates the export.
"""

from deepspeed_tpu.observability import metrics as _metrics  # noqa: F401
from deepspeed_tpu.observability.flight_recorder import (FlightRecorder,
                                                         list_postmortems,
                                                         load_postmortem,
                                                         write_postmortem)
from deepspeed_tpu.observability.memory import (MemoryLedger,
                                                capture_cost_analysis,
                                                capture_memory_analysis,
                                                kv_occupancy,
                                                make_occupancy_provider,
                                                tenant_occupancy)
from deepspeed_tpu.observability.registry import (MetricSpec,
                                                  MetricsRegistry,
                                                  default_registry)
from deepspeed_tpu.observability.tracer import (Tracer, annotate,
                                                enable_device_annotations,
                                                load_chrome_trace,
                                                merge_events, mint_trace_id,
                                                open_span, process_tracer,
                                                step_annotation,
                                                write_chrome_trace)

__all__ = ["FlightRecorder", "MemoryLedger", "MetricSpec", "MetricsRegistry",
           "Tracer", "annotate", "capture_cost_analysis",
           "capture_memory_analysis", "default_registry",
           "enable_device_annotations", "kv_occupancy", "list_postmortems",
           "load_chrome_trace", "load_postmortem", "make_occupancy_provider",
           "merge_events", "mint_trace_id", "open_span", "process_tracer",
           "step_annotation",
           "tenant_occupancy", "write_chrome_trace", "write_postmortem"]
