"""Request-scoped distributed tracing for the serving stack.

The reference ships a ``profiling/`` layer plus a comms logger; this is
the TPU-serving equivalent: one low-overhead host-side :class:`Tracer`
whose spans thread a ``trace_id`` through every hop a request takes —
scheduler ticks, replica incarnations (kill → replay), rolling-restart
migrations, and disaggregated prefill→decode KV handoffs — and export as
Chrome/Perfetto trace-event JSON so one request's life is ONE connected
timeline however many processes served it.

Design constraints (the decode fast tick must stay <2% slower traced):

* **ring buffer** — spans land in a fixed-capacity ring; a long-running
  replica never grows host memory per span, and the most recent window
  doubles as the crash flight recorder's evidence
  (:mod:`deepspeed_tpu.observability.flight_recorder`);
* **no locks on the hot path** — record construction + a single
  list-slot store per span, both atomic under the GIL; the only
  synchronisation is at export time (a snapshot copy);
* **monotonic clock** — ``time.monotonic_ns``; wall-clock anchoring
  happens once per tracer so merged multi-process traces line up;
* **id hygiene across incarnations** — span ids carry a per-tracer
  random prefix, so two incarnations of a replica (fresh Tracer each)
  can contribute spans to the SAME ``trace_id`` without id collisions.

One span mechanism, down to where the work happens.  The scheduler hands
its tracer to the engine (``ContinuousBatchScheduler(engine, tracer=)`` /
``attach_tracer``); both open every span of a tick with :func:`open_span`
(:meth:`Tracer.span` on a live tracer), which uses the innermost span
still open as the default parent, so a span records the span that caused
it without handles being threaded through calls.  A counter is recorded
once, on the span that owns it, and only where something reads it.  Span
catalogue of one scheduler tick:

=======================  ==========================  ========  =================
span                     site                        parent    attrs (counters)
=======================  ==========================  ========  =================
``tick``                 ``scheduler.step``          —         ``tick`` (number)
                                                               and, closing,
                                                               ``kind`` of the
                                                               program the tick
                                                               LAUNCHED (decode
                                                               / mixed / prefill
                                                               / verify) and
                                                               ``emitted``
                                                               (tokens the call
                                                               returns)
``pack``                 ``_tick_level`` /           tick      closing:
                         ``_tick_ahead``: the batch            ``queued``
                         the tick will launch                  (requests still
                                                               waiting to be
                                                               admitted or
                                                               resumed once it
                                                               is packed) and,
                                                               when there are
                                                               any and the
                                                               prefills were
                                                               packed,
                                                               ``held_by``: the
                                                               first rule of
                                                               ``_pack_prefills``
                                                               that held one
                                                               back (``budget``
                                                               / ``rows`` /
                                                               ``slots`` /
                                                               ``kv``)
``prefill``              the tick's ragged batch:    tick      around a launch
                         ``engine.prepare`` (under             of greedy rows,
                         the program before it,                closing:
                         when one is in flight),               ``ragged_steps``
                         the ``fetch`` of that                 (1) and
                         program, ``engine.launch``            ``ragged_ahead``
                         on its tokens; or a                   (1: prepared
                         ``put`` for logits                    under the program
                                                               before it and
                                                               launched on its
                                                               tokens; 0: packed
                                                               and built with
                                                               the chip empty)
``sample``               the tokens the tick hands   tick      closing:
                         out where it launched a               ``sampled`` (rows
                         ragged batch: those of the            that emitted a
                         program it retired (the               token this tick),
                         step before, or its own               ``device_sampled``
                         when nothing is due after             (of those, tokens
                         it); after a ``put`` for              that were a step
                         logits the host's sampler             program's argmax:
                                                               all when every
                                                               packed row is
                                                               greedy, else
                                                               none)
``decode``               ``_fast_decode_tick``: a    tick      closing: ``steps``
                         pure-decode tick's work:              (1), ``ahead``
                         the dispatches it makes,              (1 when the step
                         the fetch of the step it              whose tokens the
                         returns, the advance                  tick returns was
                                                               dispatched during
                                                               the tick before),
                                                               ``read_blocks``
                                                               (table blocks
                                                               that step's rows
                                                               hold: what its
                                                               attention read);
                                                               under an indexer
                                                               ``idx_keys`` and
                                                               ``sel_keys`` of
                                                               that step's rows
``retire``               ``_settle``: the wait for   tick (a   —
                         the program in flight and   decode
                         its advance, by a tick      tick
                         that has no ragged batch    after a
                         to prepare under it (a      mixed
                         pure-decode tick follows)   one) /
                         or by a path outside any    ``pack``
                         tick that frees or moves    / none
                         a sequence
``verify``               ``_speculative_decode_``    tick      —
                         ``tick``
``engine/build_batch``   ``engine.prepare``: the     prefill   ``tokens`` fed of
                         chunks, their KV slots,               the ``bucket``
                         the packed metadata on                padded to; with
                         the host                              recurrent state:
                                                               ``state_slots``
                                                               held,
                                                               ``chunk_seqs``
                                                               with a chunk in
                                                               the tile segment
                                                               and their
                                                               ``chunk_tokens``;
                                                               always
                                                               ``row_blocks``
                                                               (table blocks the
                                                               batch's one-token
                                                               rows hold); with a
                                                               latent pool row
                                                               (``kv_row``) those
                                                               two and
                                                               ``attn_pairs``
                                                               (causal query-key
                                                               pairs of those
                                                               chunks) and
                                                               ``ctx_rows``
                                                               (their end
                                                               positions: rows
                                                               to expand); a
                                                               tiled engine with
                                                               k / v pools:
                                                               ``chunk_key_steps``
                                                               (key steps the
                                                               tiled read's grid
                                                               runs, over tiles,
                                                               layers, passes)
                                                               and
                                                               ``chunk_live_key_steps``
                                                               (those that hold
                                                               a visible key);
                                                               with window
                                                               layers their
                                                               share of both,
                                                               ``..._win``;
                                                               two groups
                                                               (``kv_groups``):
                                                               ``read_blocks_win``
                                                               / ``read_keys_win``
                                                               (in-band blocks /
                                                               keys of one-token
                                                               rows x window
                                                               layers),
                                                               ``attn_pairs_win``
                                                               / ``ctx_rows_win``
                                                               (banded pairs /
                                                               band rows of the
                                                               chunks, a layer)
                                                               (a latent row:
                                                               ``latent_key_steps``
                                                               and
                                                               ``latent_live_key_steps``,
                                                               the expanded
                                                               read's grid over
                                                               tiles and layers)
                                                               ; under a sparse-
                                                               attention indexer
                                                               (the model's
                                                               ``index_topk``)
                                                               in their place,
                                                               a layer:
                                                               ``idx_keys`` /
                                                               ``sel_keys``
                                                               (positions the
                                                               one-token rows
                                                               score, ``p + 1``,
                                                               and read,
                                                               ``min(p + 1, k)``)
                                                               and ``idx_pairs``
                                                               / ``sel_pairs``
                                                               (the same sums
                                                               over the chunks'
                                                               rows)
``engine/upload``        ``engine.launch``: the      prefill   —
                         late rows' tokens into
                         the metadata, its ONE
                         upload
``engine/ragged_step``   the step's dispatch         prefill   the launch record
                                                               (below):
                                                               ``launch``,
                                                               ``program``
``engine/fetch_logits``  ``device_get(logits)``: the prefill   ``launch``: the
                         wait that retires the                 launch whose
                         batch's launch, when a                result it
                         packed row is stochastic              blocked on
                         (or ``put`` was called for
                         logits)
``engine/decode_prep``   ``decode_step``: KV slots,  decode    ``seqs``: live
                         table upload, token array             rows of the step;
                                                               with recurrent
                                                               state:
                                                               ``state_slots``
                                                               held and their
                                                               ``state_bytes``
                                                               of the pool's
                                                               ``state_bytes_total``
``engine/decode_step``   the step's dispatch: of     decode    the launch record
                         the step the tick returns
                         unless that is in flight,
                         and of the step after it
                         when the next tick decodes
                         the rows that go on (all
                         of them, or those the
                         returned step does not end
                         by length: so 0, 1 or 2 a
                         tick, one a tick over a
                         run of decode ticks)
``engine/verify_step``   the step's dispatch         verify    the launch record
``fetch``                ``scheduler._fetch``: the   decode /  ``launch``: the
                         blocking ``device_get`` of  verify /  launch it retires;
                         the step the tick returns:  prefill / a model with
                         when that step is ahead,    retire    ``step_counters``
                         the wait for a program                (its router
                         dispatched a tick earlier,            decides them on
                         what the host's own work              the device, they
                         since did not cover (under            cross behind the
                         ``prefill``: between the              tokens):
                         build of the next ragged              ``moe_slots``
                         batch and its launch).                (real rows x
                                                               top-k x routed
                                                               layers of that
                                                               launch),
                                                               ``moe_zero_slots``
                                                               (those that chose
                                                               a zero-compute
                                                               expert),
                                                               ``moe_held_rows``
                                                               (those whose
                                                               expert is held
                                                               here)
                         ``put(greedy=True)``: the
                         wait for a ragged batch's
                         ``int32[max_seqs]`` argmax
``advance``              ``_advance_emitted``, the   decode /  —
                         verify acceptance loop      verify /
                                                     sample /
                                                     retire
=======================  ==========================  ========  =================

The launch record.  The engine numbers every step program it dispatches
(``engine.last_launch``: one integer, engine-wide, from 1).  Each of the
three dispatch spans closes with ``launch`` (that number) and ``program``
(the ``__name__`` of the jitted function: ``decode_step``,
``ragged_step_T1088_tiled``, ``verify_step_K4``, letter for letter the
``jit(<program>)`` that heads the ``op_name`` of its device operations and,
as ``jit_<program>``, names its executions on a profile's "XLA Modules"
line).  The first dispatch record with a ``program`` is the launch that
loads that executable onto the chip and, unless something built it ahead,
builds it (the build's ``setup/build_program`` record then closes inside
the span's interval: "Once a process", below); every later launch of the
program only runs it.  The wait that retires a launch, ``fetch`` or
``engine/fetch_logits``, closes with the ``launch`` it blocked on; the
device runs launches in order, so that wait retires every earlier one too
(a prefill chunk that drains no sequence is fetched by nobody).  A
dispatch on the host is joined to its execution on the device by these
two, not by order or by a clock.

A request's own spans.  One phase is open a live request, from ``submit``
to its end, under the request's own ``trace_id`` (minted at the first
submit and carried across replicas), opened with :meth:`Tracer.start`
(no parent: they overlap the ticks) by ``scheduler._open_req_span``, which
closes the phase before:

=====================  ==========================  =======================
span / instant         site                        attrs (counters)
=====================  ==========================  =======================
``request/submit``     ``submit``, beside the      ``uid``,
(instant)              open of ``request/queued``  ``prompt_tokens``
``request/queued``     ``submit`` -> ``_admit``;   —
                       opened again by
                       ``_preempt`` (the request
                       waits to be resumed)
``request/prefill``    ``_admit`` -> the first     closing: ``chunks``
                       token handed out            (step programs that
                       (``_advance_emitted``       carried a chunk of its
                       opens ``request/decode``,   prompt), ``first_launch``
                       or ``_finish`` when that    and ``last_launch`` (the
                       token ends it), a           launch record's numbers
                       preemption, a failure       of the first and the
                                                   last of them),
                                                   ``behind_launch`` (the
                                                   launch in flight when
                                                   the first chunk was
                                                   packed, whose tokens
                                                   that batch was launched
                                                   on; 0: the host and the
                                                   device were level);
                                                   none of the four when no
                                                   chunk was launched;
                                                   ``outcome`` / ``reason``
                                                   unless the first token
                                                   closed it and the
                                                   request goes on
``request/decode``     the first token -> the      closing: ``tokens``
                       end                         (handed out in the
                                                   phase), ``outcome``
                                                   (finished / preempted /
                                                   failed / handoff /
                                                   ``replica_death:..``),
                                                   ``reason`` (length /
                                                   stop / deadline / ..)
``request/handoff``    ``_detach``                 ``kv`` (did the device
(instant)                                          KV travel with it)
=====================  ==========================  =======================

``first_launch`` .. ``last_launch`` join a request to the launch record
above and, through it, to the device; a request's holds in the queue are
the ``pack`` spans its ``request/queued`` span covers.

Once a process.  What happens once, before the first tick or training
step, is on a tracer of its own, :func:`process_tracer` (made on first use,
always enabled, the same records and exports; written by whichever thread
builds, so it appends under a lock and nests a thread's spans under that
thread's own), opened with :func:`open_span` like every other span:

=======================  ==========================  =======================
span                     site                        attrs (counters)
=======================  ==========================  =======================
``setup/import``         ``deepspeed_tpu/``          ``process_start_ns``
                         ``__init__.py``, top to     (when the process
                         bottom (two clock reads;    began, on this clock:
                         ``jax``'s import is inside  Linux, from
                         it when the caller had not  ``/proc/self/stat``;
                         imported it); kept beside   elsewhere the span's
                         the ring too                own open): where a
                         (``import_span``)           reader anchors
                                                     ``setup_s``
``setup/engine_init``    ``InferenceEngineV2``       none: its length is
                         ``.__init__``,              the metric
                         ``DeepSpeedEngine``         (``setup_engine_``
                         ``.__init__``               ``init_s``)
``setup/init_``          ``DeepSpeedEngine.``        none (the same
``parameters``           ``initialize_parameters``   metric)
``setup/build_program``  one a build: every          ``program`` (the jitted
                         executable JAX builds in    function's
                         the process, the eager      ``__name__``, letter
                         operations' and the         for letter a dispatch
                         harness's own among them    span's ``program``),
                         (reported when the build    ``trace_s``,
                         is over)                    ``lower_s``,
                                                     ``backend_s`` (the
                                                     phases it went
                                                     through), ``cache``
                                                     (``hit``: the
                                                     persistent cache held
                                                     the executable, then
                                                     ``retrieval_s`` too;
                                                     ``miss``: asked, then
                                                     compiled, written or
                                                     not; ``none``: no
                                                     cache directory),
                                                     ``seq`` (the build's
                                                     ordinal in the
                                                     process)
=======================  ==========================  =======================

Who reads them: ``benchmark/readers/setup_build_s.py`` (the spans' lengths:
``setup_engine_init_s``; ``trace_s + lower_s``: ``setup_trace_lower_s``;
``backend_s`` by ``cache``: ``setup_cache_read_s``, ``setup_compile_s``,
``setup_programs_compiled``; ``program``, ``retrieval_s`` and
``process_start_ns`` in its commentary and its anchor) and the operator's
view below (``seq`` and ``program``: ``time_to_first_launch_s``).  What an
engine holds on the device is ``observability/hbm_*`` and
``observability/kv_pool_bytes`` (``occupancy()``), not an attr here.

The build records are folded from ``jax.monitoring``'s events by the
program's ONE listener (:func:`install_build_listener`, at the bottom of
``deepspeed_tpu/__init__.py``; ``analysis/trace_guard.py`` and
``chip_smoke.py`` read their counts from it, :func:`build_totals`): each
phase's event fires when the phase ENDS, so a record's end is
the backend event's instant and its start the first phase's end less its
seconds.  The trace event names the function bare, lowering and the backend
name ``jit(<function>)``; a jitted function called inside another is traced
inside the outer's trace (those seconds are within the outer's ``trace_s``,
not added to it); the cache's events arrive between the lowering and the
backend event of the same build.  A phase JAX's in-memory caches answer
leaves no attr, and a lowering that is never compiled (``.lower()`` for the
text) no record.  A build's parent is the ``setup/*`` span its thread
holds open on the process tracer (``setup/engine_init``,
``setup/init_parameters``).  There is ONE record a build: every tracer
reads ``time.monotonic_ns``, so the span of a scheduler's tracer that
caused a build (inside the warm-up ladder, or inside the window against the
contract) is the innermost one whose interval holds the record's
``t1_ns``: ``tick`` -> ``prefill`` -> ``engine/ragged_step`` (``decode`` ->
``engine/decode_step``); the benchmark's reader names it so, and
``merge_events`` puts both tracers on one timeline (``tools/obs_dump.py``).
The operator's view of the same records: ``observability/
programs_built``, ``program_build_seconds``, ``program_cache_misses`` and
``time_to_first_launch_s`` (:func:`build_telemetry_from_here`, in the engines'
registry providers).  Cost: the listener runs only when JAX builds
something; nothing on the path of a tick or a training step reads it.

Device scopes (``jax.named_scope``) are opened where the layer is written:
``attn/*`` in ``inference/v2/modules/attention.py``, ``moe/router`` and
``moe/shared`` in ``modules/moe.py``, the other ``moe/*`` in ``ops/
grouped_gemm.py``, a family's own in ``model_implementations/ragged_*.py``.
A stack of layers that runs several times a token (``ragged_ouro.py``) is
ONE loop in the step program: its layers keep the names above under one
enclosing scope, ``loop/layers_<i>/attn/qkv`` ... ``loop/layers_<i>/mlp``,
whatever the pass, and the norm between the passes is ``pass_norm``, so a
reader that searches ``/attn/dense_read/`` finds every pass's and one that
searches ``/loop/`` tells the looped body from ``embed`` and ``lm_head``.
Such an engine's dispatch spans (``engine/ragged_step``,
``engine/decode_step``) close with ``passes`` and ``cache_layers`` beside
``launch`` / ``program``, and with what the launch asks of every cache
layer: ``loop_seqs``, ``loop_tokens``, ``loop_ctx_tokens``,
``loop_attn_pairs``.
A state-space (Mamba) layer (``ragged_jamba.py``) is ``layers_<i>/mamba/
in_proj`` (norm and ``W_in``), ``mamba/conv``, ``mamba/x_proj`` (``W_x``,
the inner norms, ``W_dt``, softplus), ``mamba/scan`` (the Mosaic kernels
``_ssm_step_kernel`` / ``_ssm_chunk_kernel`` of ``ops/selective_scan.py``,
or their XLA compositions) and ``mamba/out``.  An engine with state slots
closes ``engine/decode_prep`` and ``engine/build_batch`` with
``state_bytes`` (what the held slots take) and ``state_bytes_total`` (the
pool's device arrays) beside ``state_slots``.

Host↔device alignment, one rule: a span opened with :meth:`Tracer.span`
is ALSO entered as a ``jax.profiler.TraceAnnotation`` of the same name
while :func:`enable_device_annotations` (or ``DS_DEVICE_TRACE``) is on,
nested Tracer start → annotation enter → work → annotation exit → Tracer
finish, so during a ``jax.profiler`` capture every such span is natively
on the device trace's clock (``tick`` besides keeps its ``ds_tick`` step
annotation around the dispatching part).  Spans opened with ``start`` /
``finish`` (the overlapping ``request/*`` phases) are never annotations.
A scheduler or engine nobody handed a tracer still annotates its sites.

Off-path cost: with no tracer, or a disabled one, a site is one
attribute test and the shared null context — no ``SpanHandle``, no clock
read, no annotation (:func:`annotate` is the same null context unless
annotations are on).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence


def mint_trace_id() -> str:
    """A 16-hex-char globally unique trace id (one per user request,
    minted at submit and carried across every replica incarnation)."""
    return os.urandom(8).hex()


# --------------------------------------------------------------------- #
# Device-side annotations (host↔device trace alignment)
# --------------------------------------------------------------------- #
_NULL_CM = contextlib.nullcontext()
_DEVICE_ANNOTATIONS = os.environ.get("DS_DEVICE_TRACE", "") not in ("", "0")


def enable_device_annotations(on: bool = True) -> None:
    """Turn :func:`annotate` into real ``jax.profiler.TraceAnnotation``
    brackets (named slices on the profiler's host track, aligned with
    the XLA device timeline when a ``jax.profiler`` capture is active)."""
    global _DEVICE_ANNOTATIONS
    _DEVICE_ANNOTATIONS = bool(on)


_PROFILER_CLS: Dict[str, Any] = {}


def _profiler_cls(attr: str):
    """``jax.profiler.<attr>``, looked up once (None without jax)."""
    if attr not in _PROFILER_CLS:
        try:
            import jax.profiler as jp
            _PROFILER_CLS[attr] = getattr(jp, attr)
        except Exception:  # pragma: no cover — jax-less analysis contexts
            _PROFILER_CLS[attr] = None
    return _PROFILER_CLS[attr]


def annotate(name: str):
    """Context manager bracketing a piece of host work for the profiler.
    A shared no-op unless annotations were enabled — the decode fast
    tick must not pay a TraceAnnotation allocation per step by default.
    Inside the engine and the scheduler, spans go through
    :meth:`Tracer.span`, which calls this; it stays public for callers
    that have no tracer."""
    if not _DEVICE_ANNOTATIONS:
        return _NULL_CM
    cls = _profiler_cls("TraceAnnotation")
    return _NULL_CM if cls is None else cls(name)


def step_annotation(step: int):
    """``StepTraceAnnotation`` for one scheduler tick / train step —
    groups the tick's device work under a step marker in the profiler
    timeline.  Same no-op contract as :func:`annotate`."""
    if not _DEVICE_ANNOTATIONS:
        return _NULL_CM
    cls = _profiler_cls("StepTraceAnnotation")
    return _NULL_CM if cls is None else cls("ds_tick", step_num=step)


# --------------------------------------------------------------------- #
# Spans
# --------------------------------------------------------------------- #
class SpanHandle:
    """An OPEN span.  Close it with :meth:`Tracer.finish` (or use the
    :meth:`Tracer.span` context manager).  Cheap on purpose."""

    __slots__ = ("name", "tid", "trace_id", "span_id", "parent",
                 "t0_ns", "attrs")

    def __init__(self, name, tid, trace_id, span_id, parent, t0_ns, attrs):
        self.name = name
        self.tid = tid
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent = parent
        self.t0_ns = t0_ns
        self.attrs = attrs


class _Span:
    """What :meth:`Tracer.span` returns on an enabled tracer."""

    __slots__ = ("tr", "args", "h", "outer", "ann")

    def __init__(self, tr, name, trace_id, parent, tid, attrs):
        self.tr = tr
        self.args = (name, trace_id, parent, tid, attrs)

    def __enter__(self) -> SpanHandle:
        tr = self.tr
        name, trace_id, parent, tid, attrs = self.args
        outer = self.outer = tr._current
        if outer is not None:
            if parent is None:
                parent = outer.span_id
            if trace_id is None:
                trace_id = outer.trace_id
            if tid is None:
                tid = outer.tid
        h = self.h = tr.start(name, trace_id=trace_id, parent=parent,
                              tid=tid, attrs=attrs)
        tr._current = h
        self.ann = ann = annotate(name)
        if ann is not _NULL_CM:
            ann.__enter__()
        return h

    def __exit__(self, *exc) -> None:
        if self.ann is not _NULL_CM:
            self.ann.__exit__(*exc)
        self.tr._current = self.outer
        self.tr.finish(self.h)


def open_span(tracer: Optional["Tracer"], name: str, **kw):
    """``tracer.span(name, **kw)`` on a live tracer.  Without one (None,
    or disabled): the bare profiler annotation while those are on, so a
    capture of an engine nobody handed a tracer still shows its
    brackets; else the shared null context.  ``with ... as h``: a site
    that records counters sets ``h.attrs`` when ``h`` is a
    :class:`SpanHandle`."""
    if tracer is not None and tracer.enabled:
        return tracer.span(name, **kw)
    return annotate(name)


class Tracer:
    """Bounded-ring span recorder; see module doc.

    ``enabled=False`` makes every record call a cheap early return —
    ``start`` handles still mint ids so trace continuity survives a
    disable/enable window (e.g. a bench's untraced A arm); ``span`` is
    the shared null context.
    """

    def __init__(self, capacity: int = 4096, enabled: bool = True,
                 tid: str = "main"):
        if capacity < 1:
            raise ValueError("Tracer capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self.default_tid = tid
        #: per-tracer random prefix keeps span ids unique when several
        #: tracers (replica incarnations, processes) feed one trace
        self._sid_prefix = os.urandom(4).hex()
        self._sid_counter = itertools.count(1)
        #: the ring: fixed-size slot store, monotone write index
        self._ring: List[Optional[dict]] = [None] * capacity
        self._n = 0                         # total records ever written
        #: open spans by span_id (closed ones move to the ring)
        self._open: Dict[str, SpanHandle] = {}
        #: innermost span open through :meth:`span`: the default parent
        self._current: Optional[SpanHandle] = None
        #: wall-clock anchor: wall seconds at monotonic t0 — lets a
        #: merged multi-process trace share one absolute axis
        self._mono0_ns = time.monotonic_ns()
        self._wall0_s = time.time()
        self.dropped = 0                    # ring overwrites (telemetry)

    # -- recording ------------------------------------------------------ #
    def _mint_span_id(self) -> str:
        return f"{self._sid_prefix}{next(self._sid_counter):x}"

    def start(self, name: str, *, trace_id: Optional[str] = None,
              parent: Optional[str] = None, tid: Optional[str] = None,
              attrs: Optional[dict] = None) -> SpanHandle:
        """Open a span; returns its handle (``handle.span_id`` is the
        parent id for children)."""
        h = SpanHandle(name, tid or self.default_tid, trace_id,
                       self._mint_span_id(), parent,
                       time.monotonic_ns(), attrs)
        if self.enabled:
            self._open[h.span_id] = h
        return h

    def finish(self, h: SpanHandle,
               attrs: Optional[dict] = None) -> None:
        if not self.enabled:
            return
        self._open.pop(h.span_id, None)
        a = h.attrs
        if attrs:
            a = {**(a or {}), **attrs}
        self._append({
            "name": h.name, "ph": "X", "tid": h.tid,
            "trace_id": h.trace_id, "span_id": h.span_id,
            "parent": h.parent, "t0_ns": h.t0_ns,
            "t1_ns": time.monotonic_ns(),
            **({"attrs": a} if a else {})})

    def span(self, name: str, *, trace_id: Optional[str] = None,
             parent: Optional[str] = None, tid: Optional[str] = None,
             attrs: Optional[dict] = None):
        """Context manager around one nested piece of work; ``with ... as
        h`` gives the open handle (set ``h.attrs`` inside the block to
        record counters on it).  ``parent`` / ``trace_id`` / ``tid``
        default to those of the innermost span still open through this
        method, so a callee's span records the span that caused it
        without the caller threading handles (one thread drives a
        tracer's context-manager spans: the scheduler loop).  While
        device annotations are on it is also a
        ``jax.profiler.TraceAnnotation`` of the same name, entered after
        the span starts and left before it finishes.  On a disabled
        tracer it is the shared null context: ``h`` is None, nothing is
        built and no clock is read."""
        if not self.enabled:
            return _NULL_CM
        return _Span(self, name, trace_id, parent, tid, attrs)

    def instant(self, name: str, *, trace_id: Optional[str] = None,
                parent: Optional[str] = None, tid: Optional[str] = None,
                attrs: Optional[dict] = None) -> None:
        """A zero-duration event (submit, preempt, conviction...)."""
        if not self.enabled:
            return
        self._append({
            "name": name, "ph": "i", "tid": tid or self.default_tid,
            "trace_id": trace_id, "span_id": self._mint_span_id(),
            "parent": parent, "t0_ns": time.monotonic_ns(),
            **({"attrs": attrs} if attrs else {})})

    def _append(self, rec: dict) -> None:
        i = self._n
        if i >= self.capacity and self._ring[i % self.capacity] is not None:
            self.dropped += 1
        self._ring[i % self.capacity] = rec
        self._n = i + 1

    # -- reading -------------------------------------------------------- #
    def __len__(self) -> int:
        return min(self._n, self.capacity)

    def records(self, tail: Optional[int] = None) -> List[dict]:
        """Ring contents oldest→newest (a snapshot copy), optionally only
        the most recent ``tail`` records."""
        n = self._n
        if n <= self.capacity:
            out = [r for r in self._ring[:n]]
        else:
            cut = n % self.capacity
            out = self._ring[cut:] + self._ring[:cut]
        out = [r for r in out if r is not None]
        if tail is not None:
            out = out[-tail:]
        return out

    def open_spans(self) -> List[SpanHandle]:
        return list(self._open.values())

    def telemetry(self) -> Dict[str, float]:
        """``observability/*`` ring-health scalars — the registry
        provider form of :attr:`dropped` (a wrapped ring used to be
        silent: records vanished and nothing counted them)."""
        return {
            "observability/dropped_spans": float(self.dropped),
            "observability/spans_recorded": float(self._n),
            "observability/spans_open": float(len(self._open)),
        }

    def clear(self) -> None:
        self._ring = [None] * self.capacity
        self._n = 0
        self._open.clear()
        self._current = None
        self.dropped = 0

    # -- export --------------------------------------------------------- #
    def _ts_us(self, t_ns: int) -> float:
        """Monotonic ns → wall-anchored µs (the trace-event ts unit)."""
        return (self._wall0_s * 1e6
                + (t_ns - self._mono0_ns) / 1e3)

    def export_events(self, tail: Optional[int] = None,
                      tid: Optional[str] = None,
                      include_open: bool = True) -> List[dict]:
        """Chrome trace-event dicts ("X" complete spans + "i" instants).
        Still-open spans export with ``args.unfinished`` (a replica died
        mid-span; the evidence must not vanish with it).  A ring that
        wrapped leads with a ``tracer/dropped_spans`` metadata event so
        a reader knows the timeline's head was overwritten, not quiet."""
        now_ns = time.monotonic_ns()
        recs = self.records(tail)
        if include_open:
            recs = recs + [{
                "name": h.name, "ph": "X", "tid": h.tid,
                "trace_id": h.trace_id, "span_id": h.span_id,
                "parent": h.parent, "t0_ns": h.t0_ns, "t1_ns": now_ns,
                "attrs": {**(h.attrs or {}), "unfinished": True},
            } for h in self._open.values()]
        out = []
        if self.dropped:
            # truncation is part of the record: phase "M" so schema
            # validators treat it as metadata, not an anonymous span
            out.append({
                "name": "tracer/dropped_spans", "ph": "M",
                "ts": self._ts_us(self._mono0_ns), "pid": os.getpid(),
                "tid": tid if tid is not None else self.default_tid,
                "args": {"dropped_spans": self.dropped,
                         "capacity": self.capacity,
                         "recorded": self._n}})
        for r in recs:
            if tid is not None and r["tid"] != tid:
                continue
            args: Dict[str, Any] = {"trace_id": r["trace_id"],
                                    "span_id": r["span_id"],
                                    "parent": r["parent"]}
            args.update(r.get("attrs") or {})
            ev = {"name": r["name"], "ph": r["ph"],
                  "ts": self._ts_us(r["t0_ns"]),
                  "pid": os.getpid(), "tid": r["tid"], "args": args}
            if r["ph"] == "X":
                ev["dur"] = max((r["t1_ns"] - r["t0_ns"]) / 1e3, 0.0)
            else:
                ev["s"] = "t"              # instant scope: thread
            out.append(ev)
        return out


# --------------------------------------------------------------------- #
# Once a process: the process tracer, the ``setup/*`` spans, one record
# an executable JAX builds
# --------------------------------------------------------------------- #
class _ProcessTracer(Tracer):
    """The :class:`Tracer` of what happens once a process: the same
    records and exports.  A scheduler's tracer has one writer; this one is
    written by whichever thread builds something (an engine made on a
    worker thread, a checkpoint thread's first copy), so a record is
    appended under a lock and the innermost open span, the default parent,
    is each thread's own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._open_here = threading.local()
        #: the ``setup/import`` record, kept beside the ring as well: a
        #: process that builds more than the ring holds still knows when
        #: it began
        self.import_span: Optional[dict] = None
        super().__init__(tid="process")

    @property
    def _current(self) -> Optional[SpanHandle]:
        return getattr(self._open_here, "span", None)

    @_current.setter
    def _current(self, h: Optional[SpanHandle]) -> None:
        self._open_here.span = h

    def _append(self, rec: dict) -> None:
        with self._lock:
            super()._append(rec)

    def records(self, tail: Optional[int] = None) -> List[dict]:
        with self._lock:
            return super().records(tail)

    def record(self, name: str, t0_ns: int, t1_ns: int,
               attrs: dict) -> dict:
        """A span that is over when it is reported, its two ends read by
        whoever reports it (JAX says how long a phase of a build took when
        the phase ends), under the span this thread holds open."""
        outer = self._current
        rec = {"name": name, "ph": "X", "tid": self.default_tid,
               "trace_id": _PROCESS_TRACE_ID,
               "span_id": self._mint_span_id(),
               "parent": outer.span_id if outer is not None else None,
               "t0_ns": t0_ns, "t1_ns": t1_ns, "attrs": attrs}
        self._append(rec)
        return rec


_PROCESS_TRACER: Optional[_ProcessTracer] = None
_PROCESS_TRACE_ID = mint_trace_id()


def process_tracer() -> _ProcessTracer:
    """The tracer of what happens once a process: the ``setup/*`` spans
    and one ``setup/build_program`` record an executable (the third table
    of the module doc).  Made on first use, always enabled."""
    global _PROCESS_TRACER
    if _PROCESS_TRACER is None:
        _PROCESS_TRACER = _ProcessTracer()
    return _PROCESS_TRACER


def setup_span(name: str):
    """Decorator: the function runs under the span ``name`` on the
    process tracer (``open_span``: a profiler annotation too while those
    are on)."""
    def deco(fn):
        @functools.wraps(fn)
        def under_span(*args, **kw):
            with open_span(process_tracer(), name,
                           trace_id=_PROCESS_TRACE_ID):
                return fn(*args, **kw)
        return under_span
    return deco


def _process_start_ns(default: int) -> int:
    """The instant this process began, on ``time.monotonic_ns``: its age
    by the kernel's account (Linux: ``starttime`` of ``/proc/self/stat``,
    clock ticks after boot, against ``CLOCK_BOOTTIME``) taken from now;
    elsewhere ``default``."""
    try:
        with open("/proc/self/stat") as f:
            after_comm = f.read().rsplit(")", 1)[1].split()
        age_s = time.clock_gettime(time.CLOCK_BOOTTIME) \
            - int(after_comm[19]) / os.sysconf("SC_CLK_TCK")
        return min(default, time.monotonic_ns() - int(age_s * 1e9))
    except (OSError, ValueError, IndexError, AttributeError):
        return default


def process_began(import_open_ns: int) -> None:
    """Bottom of ``deepspeed_tpu/__init__.py``: close ``setup/import``
    (opened at its top, two clock reads) with ``process_start_ns`` and
    install the build listener."""
    tr = process_tracer()
    if tr.import_span is not None:
        return
    tr.import_span = tr.record(
        "setup/import", import_open_ns, time.monotonic_ns(),
        {"process_start_ns": _process_start_ns(import_open_ns)})
    install_build_listener()


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class _BuildState(threading.local):
    """One thread's build in progress, between JAX's events."""

    def __init__(self):
        #: bare name -> (seconds, end): traces no lowering has claimed
        self.traces: Dict[str, tuple] = {}
        #: the program lowered last and not yet compiled
        self.lowered: Optional[dict] = None
        #: what the persistent cache said to the compile under way
        self.cache: Dict[str, Any] = {}


_BUILD = _BuildState()
_BUILD_LOCK = threading.Lock()
#: process-wide, monotone: executables built, functions traced, seconds of
#: all three phases and of the backend alone, builds the persistent cache
#: was asked for and lacked
_BUILD_TOTALS = {"programs": 0, "traces": 0, "seconds": 0.0,
                 "backend_seconds": 0.0, "misses": 0}
_LISTENING = False


def _bare(fun_name: Optional[str]) -> str:
    """``jit(decode_step)`` / ``pmap(f)`` -> ``decode_step`` / ``f``: the
    trace event names the function bare, lowering and the backend name
    the module."""
    name = fun_name or "?"
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1:-1]
    return name


def _on_build_seconds(event: str, seconds: float, **kw) -> None:
    """jax.monitoring's duration events, folded into one record a build.
    Each fires at the END of its phase: end = now, start = end - seconds.
    A jitted function called inside another is traced inside the outer's
    trace (its seconds are within the outer's and are not added); only
    the function that is lowered next becomes a program."""
    st = _BUILD
    if event == _TRACE_EVENT:
        with _BUILD_LOCK:
            _BUILD_TOTALS["traces"] += 1
        st.traces[kw.get("fun_name") or "?"] = (seconds,
                                                time.monotonic_ns())
    elif event == _LOWER_EVENT:
        now = time.monotonic_ns()
        program = _bare(kw.get("fun_name"))
        low = st.lowered = {"program": program, "lower_s": seconds,
                            "t0_ns": now - int(seconds * 1e9)}
        traced = st.traces.pop(program, None)
        if traced is not None:
            low["trace_s"] = traced[0]
            low["t0_ns"] = traced[1] - int(traced[0] * 1e9)
        st.traces.clear()
        st.cache = {}
    elif event == _BACKEND_EVENT:
        _close_build(st, _bare(kw.get("fun_name")), seconds)
    elif event == _CACHE_RETRIEVAL_EVENT:
        st.cache["retrieval_s"] = seconds


def _on_build_event(event: str, **_kw) -> None:
    if event == _CACHE_ASKED_EVENT:
        # JAX asks its cache layer with or without a directory behind it
        import jax

        if jax.config.jax_compilation_cache_dir:
            _BUILD.cache["cache"] = "miss"  # asked; a hit says so next
    elif event == _CACHE_HIT_EVENT:
        _BUILD.cache["cache"] = "hit"


def _close_build(st: _BuildState, program: str, backend_s: float) -> None:
    """The backend event closes the record, on the process tracer."""
    now = time.monotonic_ns()
    low, cache = st.lowered, st.cache
    st.lowered, st.cache = None, {}
    if low is None or low["program"] != program:    # compiled ahead of
        low = {"t0_ns": now - int(backend_s * 1e9)}  # time: ``.compile()``
    attrs = {"program": program}
    for k in ("trace_s", "lower_s"):
        if k in low:
            attrs[k] = low[k]
    attrs["backend_s"] = backend_s
    attrs["cache"] = cache.get("cache", "none")
    if attrs["cache"] == "hit" and "retrieval_s" in cache:
        attrs["retrieval_s"] = cache["retrieval_s"]
    with _BUILD_LOCK:
        totals = _BUILD_TOTALS
        totals["programs"] += 1
        totals["backend_seconds"] += backend_s
        totals["seconds"] += backend_s + low.get("trace_s", 0.0) \
            + low.get("lower_s", 0.0)
        totals["misses"] += attrs["cache"] == "miss"
        attrs["seq"] = totals["programs"]
    process_tracer().record("setup/build_program", low["t0_ns"], now, attrs)


def install_build_listener() -> None:
    """The program's ONE ``jax.monitoring`` listener (its two
    registrations: durations and plain events), once a process."""
    global _LISTENING
    if _LISTENING:
        return
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_build_seconds)
    jax.monitoring.register_event_listener(_on_build_event)
    _LISTENING = True


def build_totals() -> Dict[str, float]:
    """``programs`` built, functions traced (``traces``), ``seconds`` of
    trace + lowering + backend, ``backend_seconds`` of the backend alone
    (compile, or the persistent cache's read) and persistent-cache
    ``misses`` since the listener was installed; monotone (snapshot and
    subtract)."""
    install_build_listener()
    with _BUILD_LOCK:
        return dict(_BUILD_TOTALS)


def build_telemetry_from_here() -> Callable[[Iterable[str]], Dict[str, float]]:
    """For an engine's constructor.  Returns ``telemetry(programs)``: the
    ``observability/program*`` counters of this process and, once one of
    the engine's step programs (named ``programs``) has been built after
    this call, ``observability/time_to_first_launch_s``: ``setup/import``'s
    open to the end of that build, the instant the engine's first dispatch
    reached the device (looked up in the process tracer's ring until it is
    found, then kept).  Host-side reads only."""
    after_seq = build_totals()["programs"]
    found: List[float] = []

    def telemetry(programs: Iterable[str]) -> Dict[str, float]:
        totals = build_totals()
        out = {"observability/programs_built": float(totals["programs"]),
               "observability/program_build_seconds": totals["seconds"],
               "observability/program_cache_misses": float(totals["misses"])}
        tr = process_tracer()
        if not found and tr.import_span is not None \
                and totals["programs"] > after_seq:
            names = set(programs)
            for r in tr.records():
                a = r.get("attrs") or {}
                if r["name"] == "setup/build_program" \
                        and a["seq"] > after_seq and a["program"] in names:
                    found.append(
                        (r["t1_ns"] - tr.import_span["t0_ns"]) / 1e9)
                    break
        if found:
            out["observability/time_to_first_launch_s"] = found[0]
        return out

    return telemetry


# --------------------------------------------------------------------- #
# Trace files
# --------------------------------------------------------------------- #
def merge_events(*event_lists: Iterable[dict]) -> List[dict]:
    """Merge per-tracer/per-process event lists into one timeline,
    sorted by ts (ties by name for determinism)."""
    out: List[dict] = []
    for evs in event_lists:
        out.extend(evs)
    out.sort(key=lambda e: (e.get("ts", 0.0), e.get("name", "")))
    return out


def _tid_metadata(events: Sequence[dict]) -> List[dict]:
    """Perfetto wants integer tids; emit thread_name metadata mapping
    our string tids onto stable small ints."""
    labels: Dict[tuple, int] = {}
    for e in events:
        key = (e.get("pid", 0), e.get("tid", "main"))
        if key not in labels:
            labels[key] = len(labels)
    meta = [{"name": "thread_name", "ph": "M", "pid": pid,
             "tid": idx, "args": {"name": str(tid)}}
            for (pid, tid), idx in labels.items()]
    return meta


def write_chrome_trace(path: str, events: Sequence[dict]) -> str:
    """Write a Chrome/Perfetto-loadable trace-event JSON file (atomic:
    tmp + rename; parent dirs created)."""
    labels: Dict[tuple, int] = {}
    meta = _tid_metadata(events)
    for m in meta:
        labels[(m["pid"], m["args"]["name"])] = m["tid"]
    norm = []
    for e in events:
        e = dict(e)
        e["tid"] = labels[(e.get("pid", 0), str(e.get("tid", "main")))]
        norm.append(e)
    payload = {"traceEvents": meta + norm, "displayTimeUnit": "ms"}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def load_chrome_trace(path: str) -> List[dict]:
    with open(path) as f:
        data = json.load(f)
    if isinstance(data, dict):
        return list(data.get("traceEvents", []))
    return list(data)
