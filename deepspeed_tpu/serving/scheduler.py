"""Iteration-level continuous-batching scheduler (reference: the Orca-style
request loop DeepSpeed-MII runs above the FastGen engine —
mii/batching/ragged_batching.py ``schedule_requests`` — with Dynamic
SplitFuse packing per blogs/deepspeed-fastgen).

Each :meth:`ContinuousBatchScheduler.step` packs exactly one engine forward
under the fixed token budget:

1. every running DECODE sequence first (one token each) — decode latency is
   the SLO, so decodes are never displaced by prefill work;
2. then SplitFuse prefill chunks — mid-prefill continuations, preempted
   requests being resumed (recompute), and new admissions — each sized by
   binary search against ``engine.can_schedule()`` to fill the remaining
   budget without overcommitting KV blocks, sequence slots or the rows
   of the engine's tile-aligned layout, so the tick is ONE forward.

KV pressure: when the decode set itself no longer fits (every decode token
may need a fresh block), the scheduler preempts the lowest-priority /
most-recently-admitted running request — ``engine.flush_to_host()`` drops
its device blocks, the prompt + generated tokens stay host-side on the
:class:`Request`, and it re-admits later by recompute (re-prefilling
``prompt + generated``), which under greedy sampling reproduces the exact
unpreempted continuation.

Everything here is host-side python; device work is the engine's single
jitted ragged step — the same split the reference keeps.

What a tick fetches follows its rows' ``SamplingParams``: every step program
takes the argmax of its logits, and a tick whose packed rows are all greedy
(mixed, prefill or decode) fetches that token vector, one int32 a row; one
stochastic row and the tick fetches the logits for the (seed, uid,
position)-keyed host sampler, as every tick once did.

A greedy pure-decode tick does not wait for its own tokens before it hands
the device the next step: while the host can tell that the next tick will be
a pure-decode tick too, it dispatches that step on the device-resident
tokens of this one, then fetches this one's (:class:`_InFlight`,
``_fast_decode_tick``).  The step goes out over THE ROWS THAT GO ON: the rows
of this step whose request has not ended and does not end by length with the
token this step hands it (``_rows_going_on``; the engine gathers their tokens
into their new rows).  So the tick in which a request ends leaves a program
in flight like any other.  The device runs one program behind the other
while the host advances, packs and prepares.

A greedy ragged batch is not built with the chip empty either: with a program
in flight (a ragged step, or a decode step run ahead) and a ragged batch due
next, a tick packs and prepares that batch from the view "the program in
flight has completed" WHILE it runs (``engine.prepare``: everything but the
token values of the rows it is about to hand a token), then fetches its
tokens, checks them against those rows' stops, launches the prepared batch
on them (``engine.launch``) and only then hands the fetched tokens out: the
advance, the caller's loop and the next tick's packing run under the program
just launched (``_tick_ahead``).  Only the tokens wait for the device; the
device waits for the fetch, the token patch, one upload and one dispatch.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from deepspeed_tpu.inference.v2.speculative import (SpeculativeConfig,
                                                    SpeculativeStats,
                                                    accept_drafts)
from deepspeed_tpu.observability.tracer import (SpanHandle, Tracer,
                                                mint_trace_id, open_span,
                                                step_annotation)
from deepspeed_tpu.resilience import chaos
from deepspeed_tpu.resilience.heartbeat import Heartbeat
from deepspeed_tpu.serving.metrics import ServingMetrics
from deepspeed_tpu.serving.request import (Request, RequestState,
                                           SamplingParams)
from deepspeed_tpu.serving.sampler import sample_batch
from deepspeed_tpu.utils.logging import logger

class QueueFullError(RuntimeError):
    """``submit()`` rejected: the admission queue is at ``max_queue``.
    Back off and retry (or shed load) — the queue will not grow without
    bound under overload."""


class TickDeadlineError(RuntimeError):
    """The tick watchdog tripped: one scheduler tick (engine forward +
    sample) exceeded ``tick_deadline_s``.  Carries the packed batch's
    uids so the fleet's crash-blame tracker can attribute the stall to
    the requests that were actually in the forward — a slow-but-
    returning tick is *detected here* (the scheduler still beats its
    heartbeat), while a truly wedged forward never returns and is the
    supervisor's hang detector's job."""

    def __init__(self, uids, elapsed_s: float, deadline_s: float):
        super().__init__(
            f"scheduler tick blew its {deadline_s:.3f}s deadline "
            f"({elapsed_s:.3f}s) with uids {sorted(uids)} in the batch")
        self.uids = list(uids)
        self.elapsed_s = elapsed_s
        self.deadline_s = deadline_s


@dataclasses.dataclass
class _InFlight:
    """A greedy step program whose tokens are still on the device: the
    scheduler's one piece of state about a program in flight.  A
    ``decode_step`` hands every row of ``packed`` a token; a ragged step
    (``ragged``) those whose feed it completes.  Of those rows, the ones
    that go on (``_rows_going_on``: not ended, and not ended by length
    with that token) are the rows of whatever is sent behind it: the next
    decode step, whose ``packed`` / ``rows`` are they in their new order,
    or the late rows of the ragged batch prepared under it."""

    #: the requests it was packed from, in row order
    packed: List[Request]
    #: the requests it hands a token, each with its row of ``nxt``
    rows: List[Tuple[Request, int]]
    #: ``int32[max_seqs]`` on the device
    nxt: Any
    #: 1 when it was dispatched on the tokens of the program before it, by
    #: the tick before the one that returns its tokens (a decode step), or
    #: prepared under that program and launched on its tokens (a ragged
    #: step)
    ahead: int
    #: the engine's number for this launch (``engine.last_launch`` right
    #: after the dispatch): the ``fetch`` that retires it closes with it
    launch: int
    #: a ragged step (``engine.launch``): ``Request.fed`` counts its chunks
    #: since its dispatch; a decode step's token is counted when consumed
    ragged: bool = False


class ContinuousBatchScheduler:
    """Owns the request lifecycle between user ``submit()`` calls and
    :class:`~deepspeed_tpu.inference.v2.engine_v2.InferenceEngineV2`."""

    def __init__(self, engine, monitor=None,
                 metrics: Optional[ServingMetrics] = None,
                 export_every: int = 0,
                 max_queue: Optional[int] = None,
                 fast_decode: bool = True,
                 tick_deadline_s: Optional[float] = None,
                 speculative: Optional[SpeculativeConfig] = None,
                 tracer: Optional[Tracer] = None,
                 registry=None, registry_key: str = "serving"):
        self.engine = engine
        #: request-scoped tracing (None = zero-overhead off).  Tick
        #: phases (pack, prefill, decode/verify, sample) record as child
        #: spans under a per-tick span on the scheduler's own trace, the
        #: engine's spans and ``fetch`` / ``advance`` under the phase that
        #: caused them (catalogue: observability/tracer.py); request
        #: lifecycle spans carry each request's trace_id.
        #: The fleet re-points tracer/trace_tid at respawn so spans are
        #: tagged ``replica#incarnation``.
        self.tracer = tracer
        self.trace_tid = tracer.default_tid if tracer is not None \
            else "scheduler"
        if tracer is not None and hasattr(engine, "attach_tracer"):
            engine.attach_tracer(tracer)
        #: the tick timeline's own trace (request traces are per-request)
        self.sched_trace_id = mint_trace_id()
        #: uid -> (open request-phase SpanHandle, tokens the request had
        #: been handed when it opened); empty while nothing is traced
        self._req_spans: Dict[int, Tuple[SpanHandle, int]] = {}
        #: unified metrics registry (observability.registry): when given,
        #: this scheduler's serving/* snapshot registers as a provider
        #: under the STABLE ``registry_key`` — a respawned scheduler
        #: registering the same key supersedes its dead incarnation
        #: (an id()-keyed scheme would leak dead engines into the
        #: registry and let a stale provider shadow the live one)
        self._registry = registry
        self._registry_key = registry_key
        if registry is not None:
            registry.register_provider(registry_key, self.telemetry)
            # live occupancy gauges (observability/kv_*, hbm_*,
            # tenant_tokens_*): host-side bookkeeping reads only, so a
            # scrape between steady-state decode ticks stays
            # 0-recompile/0-sync (TraceGuard-asserted in tier-1)
            if hasattr(engine, "state_manager") \
                    and hasattr(engine.state_manager, "kv_cache"):
                from deepspeed_tpu.observability.memory import (
                    make_occupancy_provider)

                registry.register_provider(
                    f"{registry_key}/occupancy",
                    make_occupancy_provider(engine, self))
            if tracer is not None:
                registry.register_provider(f"{registry_key}/tracer",
                                           tracer.telemetry)
        #: speculative decoding (ROADMAP item 1): pure-decode ticks run a
        #: drafter + one multi-token verify_step instead of decode_step,
        #: emitting 1..draft_k+1 tokens per weight pass; a tick with no
        #: drafts (or no KV/context room for the lookahead) falls back to
        #: the plain fast decode tick
        if speculative is not None:
            if not hasattr(engine, "verify_step"):
                raise ValueError(
                    "speculative decoding needs an engine with "
                    "verify_step/commit_verified (InferenceEngineV2)")
            engine.state_manager.require(
                "verify", "speculative decoding (drafts are verified "
                "through verify_step)")
            if not fast_decode:
                raise ValueError(
                    "speculative decoding runs on the fast decode tick — "
                    "fast_decode=False would silently never speculate")
        self.speculative = speculative
        self.spec_stats = SpeculativeStats()
        #: acceptance-aware K autotuning (speculative.autotune_k): per-
        #: request accept-rate EWMA and the effective K it currently
        #: prescribes (both dropped when the request terminalizes)
        self._spec_accept_ewma: Dict[int, float] = {}
        self._spec_k: Dict[int, int] = {}
        #: runtime degradation knobs (fleet/brownout.py): a draft-K cap
        #: that squeezes speculation without touching config, a master
        #: speculative enable, and tightened admission caps — all
        #: reversible through the set_* setters below
        self.spec_k_cap: Optional[int] = None
        self._speculative_enabled = True
        self.admit_max_new_tokens: Optional[int] = None
        self.admit_max_context: Optional[int] = None
        #: pure-decode ticks go through ``engine.decode_step`` — block
        #: tables/positions stay device-resident across ticks and the
        #: only host transfer is the sampled-token fetch, instead of a
        #: full metadata pack+upload and an [S, vocab] logits download
        #: per tick (the put()-path cost the bench's put_decode_step_ms
        #: measures)
        self.fast_decode = fast_decode and hasattr(engine, "decode_step")
        self.fast_ticks = 0
        #: ragged batches prepared under the program before them and
        #: launched on its tokens, and preparations dropped because a late
        #: row's token was one of its stop tokens
        self.ragged_ahead_ticks = 0
        self.ragged_discards = 0
        #: the program whose tokens a later tick will return (None: the
        #: host and the device are level): a decode step dispatched ahead,
        #: or a ragged step launched by the tick before.  While it is set
        #: the engine's positions are ahead of what the requests were
        #: handed, and every path that frees or moves a sequence outside
        #: the tick that returns it calls ``_settle`` first.
        self._inflight: Optional[_InFlight] = None
        #: tokens a ``_settle`` outside the owning tick's decode phase
        #: handed out; the next ``step`` returns them before its own
        self._early: List[Tuple[Request, int]] = []
        #: how long the last tick took, for "is a deadline due before the
        #: next one"
        self._tick_s = 0.0
        sm_cfg = engine.config.state_manager
        self.token_budget = sm_cfg.max_ragged_batch_size
        #: the configured budget, for set_token_budget(None) to restore
        self._base_token_budget = self.token_budget
        self.max_seqs = sm_cfg.max_ragged_sequence_count
        self.max_context = sm_cfg.max_context
        self.metrics = metrics if metrics is not None \
            else ServingMetrics(monitor)
        #: export serving/* scalars through the monitor every N ticks
        #: (0 = only on run_until_idle/drain completion)
        self.export_every = export_every
        if max_queue is not None and max_queue < 1:
            raise ValueError("max_queue must be >= 1 (or None for unbounded)")
        #: bounded admission: submit() raises QueueFullError past this
        self.max_queue = max_queue
        if tick_deadline_s is not None and tick_deadline_s <= 0:
            raise ValueError("tick_deadline_s must be > 0 (or None)")
        #: tick watchdog: a tick slower than this raises
        #: :class:`TickDeadlineError` naming the packed batch, AFTER the
        #: engine returns (a wedged forward that never returns is the
        #: supervisor heartbeat detector's case, not this one)
        self.tick_deadline_s = tick_deadline_s
        self.tick_deadline_trips = 0
        self._queued: List[Request] = []
        self._running: Dict[int, Request] = {}
        self._preempted: List[Request] = []
        self._finished: List[Request] = []
        #: uids of every non-terminal request — O(1) collision probes for
        #: auto-uid allocation (here and in the fleet router)
        self._live_uids: set = set()
        self._uid_counter = itertools.count(1)
        self._admit_counter = itertools.count()
        #: summed _work() of queued+preempted requests — frozen while
        #: parked (no feeding/decoding), maintained at the five bucket
        #: transitions so backlog_tokens() never walks the queue
        self._parked_backlog = 0
        self._tick = 0
        #: set by shutdown(): admission is closed for good
        self._shutting_down = False
        #: liveness ticker for the job supervisor's hang detector (one
        #: beat per scheduler tick; a wedged engine forward goes stale)
        self._heartbeat = Heartbeat.from_env()

    # ------------------------------------------------------------------ #
    # Runtime degradation knobs (brownout)
    # ------------------------------------------------------------------ #
    @property
    def _spec_active(self):
        """The speculative config when speculation is enabled right now
        (brownout stage 3 flips the enable without losing the config)."""
        return self.speculative if self._speculative_enabled else None

    def set_speculative_enabled(self, enabled: bool) -> None:
        """Disable/re-enable speculative decoding at runtime.  A no-op
        on schedulers built without a speculative config."""
        self._settle()      # the verify path packs its own decode ticks
        self._speculative_enabled = bool(enabled)

    def set_spec_k_cap(self, cap: Optional[int]) -> None:
        """Cap the effective draft K below the configured ``draft_k``
        (None restores).  Shrinks the verify lookahead immediately —
        the pass's gamma follows the longest draft actually proposed."""
        if cap is not None and cap < 1:
            raise ValueError("spec_k_cap must be >= 1 (or None)")
        self.spec_k_cap = cap

    def set_token_budget(self, budget: Optional[int]) -> None:
        """Cap the per-tick prefill token budget (None restores the
        configured ``max_ragged_batch_size``).  Caps only — the budget
        never rises above the compiled batch geometry."""
        if budget is None:
            self.token_budget = self._base_token_budget
        elif budget < 1:
            raise ValueError("token_budget must be >= 1 (or None)")
        else:
            self.token_budget = min(budget, self._base_token_budget)

    def set_admission_caps(self, max_new_tokens: Optional[int] = None,
                           max_context: Optional[int] = None) -> None:
        """Tighten admission at runtime: clamp each new request's
        ``max_new_tokens`` and reject prompts longer than the tightened
        context cap with a retryable :class:`QueueFullError` (None/None
        restores).  Already-admitted requests are untouched."""
        if max_new_tokens is not None and max_new_tokens < 1:
            raise ValueError("admit_max_new_tokens must be >= 1 (or None)")
        if max_context is not None and max_context < 2:
            raise ValueError("admit_max_context must be >= 2 (or None)")
        self.admit_max_new_tokens = max_new_tokens
        self.admit_max_context = max_context

    # ------------------------------------------------------------------ #
    # Submission
    # ------------------------------------------------------------------ #
    def submit(self, prompt: Optional[Sequence[int]] = None,
               sampling: Optional[SamplingParams] = None,
               priority: int = 0, uid: Optional[int] = None,
               on_token=None, deadline_s: Optional[float] = None,
               request: Optional[Request] = None,
               trace_id: Optional[str] = None) -> Request:
        """Enqueue one generation request; returns the tracked
        :class:`Request` (read its ``state``/``generated`` as it runs)."""
        if request is None:
            if prompt is None:
                raise ValueError("submit: prompt or request required")
            if uid is None:
                # auto uids skip anything live (a caller-supplied uid may
                # have claimed a counter value)
                uid = next(self._uid_counter)
                while self._is_tracked_uid(uid):
                    uid = next(self._uid_counter)
            request = Request(
                uid=uid,
                prompt=[int(t) for t in prompt],
                sampling=sampling or SamplingParams(),
                priority=priority, deadline_s=deadline_s,
                on_token=on_token, trace_id=trace_id)
        # a replayed/handed-off request keeps its original trace_id (the
        # whole point: one trace across incarnations); fresh ones mint
        if request.trace_id is None:
            request.trace_id = mint_trace_id()
        if self._shutting_down:
            self.metrics.record_reject(request)
            raise RuntimeError(
                f"submit: scheduler is shutting down — request "
                f"{request.uid} rejected (admission closed)")
        if request.state is not RequestState.QUEUED:
            raise ValueError(f"submit: request {request.uid} already "
                             f"{request.state.value}")
        if self._is_tracked_uid(request.uid):
            raise ValueError(f"submit: uid {request.uid} already live")
        if self.max_queue is not None and len(self._queued) >= self.max_queue:
            self.metrics.record_reject(request)
            raise QueueFullError(
                f"submit: admission queue full ({len(self._queued)} waiting, "
                f"max_queue={self.max_queue}) — request {request.uid} "
                "rejected; retry after the queue drains")
        # brownout stage-4 admission tightening: clamp the generation
        # budget (shorter answers, not failures) and shed over-long
        # prompts with a retryable error instead of a permanent one
        if self.admit_max_new_tokens is not None \
                and request.sampling.max_new_tokens \
                > self.admit_max_new_tokens:
            request.sampling.max_new_tokens = self.admit_max_new_tokens
        if self.admit_max_context is not None \
                and len(request.history) + 1 > self.admit_max_context:
            self.metrics.record_reject(request)
            raise QueueFullError(
                f"submit: history of {len(request.history)} tokens exceeds "
                f"the brownout-tightened context cap "
                f"{self.admit_max_context} — request {request.uid} "
                "rejected; retry when pressure recedes")
        # history, not prompt: a resubmitted (handed-off) request carries
        # already-generated tokens that need KV room too
        if len(request.history) + 1 > self.max_context:
            raise ValueError(
                f"submit: history of {len(request.history)} tokens cannot "
                f"fit max_context {self.max_context} with room to generate")
        sm = self.engine.state_manager
        hist_blocks = -(-(len(request.history) + 1) // sm.block_size)
        if hist_blocks > sm.allocator.num_blocks - 1:
            raise ValueError(
                f"submit: history needs {hist_blocks} KV blocks but the "
                f"pool only has {sm.allocator.num_blocks - 1} usable")
        self._queued.append(request)
        self._live_uids.add(request.uid)
        self._parked_backlog += self._work(request)
        self.metrics.record_submit(request)
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("request/submit", trace_id=request.trace_id,
                       tid=self.trace_tid,
                       attrs={"uid": request.uid,
                              "prompt_tokens": len(request.prompt)})
            self._open_req_span(request, "queued")
        return request

    def _is_tracked_uid(self, uid: int) -> bool:
        return uid in self._live_uids

    def unregister_metrics(self) -> None:
        """Detach this scheduler's providers from the registry (teardown
        of a scheduler that is NOT being superseded under its key)."""
        if self._registry is not None:
            self._registry.unregister_provider(self._registry_key)
            self._registry.unregister_provider(
                f"{self._registry_key}/occupancy")
            self._registry.unregister_provider(
                f"{self._registry_key}/tracer")

    def attach_tracer(self, tracer: Optional[Tracer],
                      tid: Optional[str] = None) -> None:
        """Point this scheduler at ``tracer``, spans tid-tagged ``tid``
        (default: the tracer's own tid).  The tracer/trace_tid pair must
        move together — this is the one place that knows that."""
        self.tracer = tracer
        if hasattr(self.engine, "attach_tracer"):
            self.engine.attach_tracer(tracer)
        if self._registry is not None:
            # a respawn's fresh tracer supersedes the dead one's ring
            # gauges under the same stable provider key; detaching
            # (tracer=None) drops the provider too — a dead ring must
            # not keep reporting (or stay pinned in memory) forever
            if tracer is not None:
                self._registry.register_provider(
                    f"{self._registry_key}/tracer", tracer.telemetry)
            else:
                self._registry.unregister_provider(
                    f"{self._registry_key}/tracer")
        if tracer is not None:
            self.trace_tid = tid if tid is not None else tracer.default_tid

    # ------------------------------------------------------------------ #
    # Request-phase spans (one open phase per live request)
    # ------------------------------------------------------------------ #
    def _open_req_span(self, req: Request, phase: str) -> None:
        """Close the request's open phase and open ``request/<phase>``:
        ``queued`` (submit, or a preemption, to admission), ``prefill``
        (admission to the first token handed out) or ``decode`` (from
        there to the end).  Kept with the tokens the request had been
        handed when the phase opened: a ``decode`` span closes with the
        ``tokens`` of the phase."""
        tr = self.tracer
        if tr is None or not tr.enabled:
            return
        self._close_req_span(req)
        self._req_spans[req.uid] = (
            tr.start(f"request/{phase}", trace_id=req.trace_id,
                     tid=self.trace_tid), len(req.generated))

    def _close_req_span(self, req: Request, **attrs) -> None:
        h, had = self._req_spans.pop(req.uid, (None, 0))
        if h is not None and self.tracer is not None:
            if h.name == "request/decode":
                attrs["tokens"] = len(req.generated) - had
            self.tracer.finish(h, attrs=attrs or None)

    def _trace_chunks(self, packed, launch: int, behind: int) -> None:
        """Traced ticks only: launch ``launch`` carries a chunk of every
        packed request whose open phase is ``request/prefill``.  That
        span closes with ``chunks`` (such step programs), the engine's
        numbers of the first and the last of them (``first_launch``,
        ``last_launch``) and ``behind_launch``: the launch that was in
        flight when the first chunk was packed (0: the host and the device
        were level)."""
        for req in packed:
            h, _ = self._req_spans.get(req.uid, (None, 0))
            if h is None or h.name != "request/prefill":
                continue
            if h.attrs is None:
                h.attrs = {"chunks": 1, "first_launch": launch,
                           "last_launch": launch, "behind_launch": behind}
            else:
                h.attrs["chunks"] += 1
                h.attrs["last_launch"] = launch

    def abort_request_spans(self, outcome: str) -> None:
        """Close every open request-phase span.  The fleet calls this on
        a replica death so the dead incarnation's spans export closed
        and tagged with the outcome instead of dangling — the request's
        NEXT incarnation opens fresh spans under the same trace_id."""
        for req in [*self._queued, *self._running.values(),
                    *self._preempted]:
            self._close_req_span(req, outcome=outcome)

    # ------------------------------------------------------------------ #
    # State inspection
    # ------------------------------------------------------------------ #
    @property
    def num_pending(self) -> int:
        """Requests not yet in a terminal state.  Non-zero while a decode
        step is in flight: its rows are running requests (one whose rows
        have all stopped is discarded when the last of them stops)."""
        return len(self._queued) + len(self._running) + len(self._preempted)

    @staticmethod
    def _work(req: Request) -> int:
        """Outstanding tokens for one request: unfed history plus
        remaining generation budget."""
        return (req.remaining_feed
                + max(req.sampling.max_new_tokens - len(req.generated), 0))

    def backlog_tokens(self) -> int:
        """Outstanding work in tokens across every non-terminal request
        (the router's load signal).  O(max_seqs), not O(queue): parked
        requests' contributions are frozen, so only the bounded running
        set is walked."""
        return self._parked_backlog + sum(
            self._work(r) for r in self._running.values())

    @property
    def finished_requests(self) -> List[Request]:
        return list(self._finished)

    @property
    def running_uids(self) -> List[int]:
        return list(self._running)

    @property
    def running_decode_uids(self) -> List[int]:
        """Running requests whose prefill completed (state DECODE) — the
        disaggregated fleet migrates exactly these off a prefill replica,
        KV in hand, the tick they finish prefilling.  Settles a decode
        step in flight first: its token may end a request, and the caller
        extracts every uid listed here."""
        self._settle()
        return [r.uid for r in self._running.values()
                if r.state is RequestState.DECODE]

    # ------------------------------------------------------------------ #
    # One scheduling tick
    # ------------------------------------------------------------------ #
    def step(self) -> List[Tuple[Request, int]]:
        """Pack one engine forward and hand out its tokens: the program's
        argmax when every packed row is greedy, a host sample of its
        fetched logits otherwise.  Returns the ``(request, token)`` pairs
        emitted this tick."""
        if self._heartbeat is not None:
            self._heartbeat.beat(self._tick)
        with open_span(self.tracer, "tick", trace_id=self.sched_trace_id,
                       tid=self.trace_tid,
                       attrs={"tick": self._tick}) as tick_h:
            emitted = self._step_traced(
                tick_h if type(tick_h) is SpanHandle else None)
        if self._early:
            emitted, self._early = self._early + emitted, []
        return emitted

    def _step_traced(self, tick_h) -> List[Tuple[Request, int]]:
        self._expire_deadlines()
        self._reap_unservable()
        step, tick = self._inflight, None
        if step is not None:
            # a ragged batch due after the program in flight is prepared
            # under it and launched on its tokens; where that view does
            # not hold, a ragged step is retired before the tick is packed
            # the ordinary way (a decode step in flight is the decode
            # tick's own to return)
            if self._ragged_due(step):
                tick = self._tick_ahead(step)
            if tick is None and step.ragged:
                self._settle()
        if tick is None:
            tick = self._tick_level()
        if tick is None:
            return []
        kind, packed, emitted, t0 = tick
        if tick_h is not None:
            # the tick span closes with what it launched and what came out
            # (with what a retired step handed out on the way)
            tick_h.attrs["emitted"] = len(emitted) + len(self._early)
            if kind is not None:    # (None: a discarded batch, and
                tick_h.attrs["kind"] = kind     # nothing after it)
        self._tick_s = elapsed = time.monotonic() - t0
        if self.tick_deadline_s is not None:
            if elapsed > self.tick_deadline_s:
                self.tick_deadline_trips += 1
                self._tick += 1
                raise TickDeadlineError([r.uid for r in packed],
                                        elapsed, self.tick_deadline_s)
        self._tick += 1
        if self.export_every and self._tick % self.export_every == 0:
            self._export_metrics()
        return emitted

    def _begin(self, packed) -> float:
        """A batch is packed and about to go to the engine; returns the
        start of what the tick watchdog times."""
        now = time.monotonic()
        for req in packed:
            if req.first_scheduled_time is None:
                req.first_scheduled_time = now
        if chaos.armed("poison_request") is not None:
            # a malformed request deterministically crashes the engine
            # the moment it is batched into a forward — the crash the
            # fleet's quarantine layer must attribute and contain
            for req in packed:
                chaos.fire("poison_request", key=str(req.uid))
        # monotonic on purpose: this is a liveness DEADLINE (host-side
        # control flow), not a device-compute timing bracket — a tick
        # that stalls on anything (engine, allocator, GIL) should trip
        t0 = time.monotonic()
        chaos.fire("tick_stall")
        return t0

    def _left_waiting(self, held_by: Optional[str]) -> Dict[str, Any]:
        """What a traced ``pack`` span closes with: ``queued``, the
        requests still waiting to be admitted or resumed once the batch is
        packed, and, when there are any and ``_pack_prefills`` ran,
        ``held_by``: its first rule that held a request back."""
        waiting = len(self._queued) + len(self._preempted)
        if waiting and held_by is not None:
            return {"queued": waiting, "held_by": held_by}
        return {"queued": waiting}

    def _tick_level(self):
        """A tick that starts with the host and the device level (or with
        a decode step in flight and no ragged batch due: the decode tick
        returns it): pack, hand the engine one forward, hand out its
        tokens.  Returns ``(kind, packed rows, emitted, watchdog start)``,
        or None when nothing could be packed.

        A ragged batch whose rows are all greedy is prepared and launched
        back to back.  With another ragged batch due after it, it stays in
        flight and the tick hands out nothing: the next tick prepares that
        batch under it and returns its tokens (``_tick_ahead``).  Otherwise
        its tokens are fetched and handed out here, as a tick always
        did."""
        uids: List[int] = []
        chunks: List[List[int]] = []
        packed: List[Request] = []

        with open_span(self.tracer, "pack") as span:
            held = None
            if self._inflight is None:
                self._pack_decodes(uids, chunks, packed)
                held = self._pack_prefills(uids, chunks, packed)
            else:
                # this tick returns the tokens of the step in flight: its
                # rows were packed a tick ago, and whatever arrived since
                # joins in the next tick
                packed = [r for r in self._inflight.packed
                          if r.finish_reason is None]
                uids = [r.uid for r in packed]
            if type(span) is SpanHandle:
                span.attrs = self._left_waiting(held)

        if not uids:
            self._handle_stall()
            return None

        t0 = self._begin(packed)
        n_decode = sum(r.state is RequestState.DECODE for r in packed)
        decode_tick = n_decode == len(packed)
        kind = "decode" if decode_tick else "mixed" if n_decode \
            else "prefill"
        # all-greedy rows: the program's own argmax is the sample
        greedy = all(r.sampling.greedy for r in packed)
        with step_annotation(self._tick):
            if self.fast_decode and decode_tick:
                emitted = None
                if self._spec_active is not None:
                    with open_span(self.tracer, "verify"):
                        emitted = self._speculative_decode_tick(
                            uids, chunks, packed)
                    if emitted is not None:
                        kind = "verify"
                if emitted is None:
                    if self._spec_active is not None:
                        self.spec_stats.fallback_ticks += 1
                    emitted = self._fast_decode_tick(uids, chunks, packed)
            elif greedy and self.fast_decode and self._spec_active is None:
                with open_span(self.tracer, "prefill") as span:
                    step = self._launch_ragged(
                        span, self.engine.prepare(uids, chunks), packed,
                        chunks, {}, behind=0)
                    due = self._ragged_due(step)
                    if not due:
                        toks = self._fetch_step(step)
                if due:
                    # stays in flight: the next tick prepares the batch
                    # after it under it, and returns its tokens
                    return kind, packed, [], t0
                self._inflight = None
                with open_span(self.tracer, "sample") as span:
                    emitted = self._advance_step(step, toks, span)
            else:
                with open_span(self.tracer, "prefill") as span:
                    out = self.engine.put(uids, chunks, sync=True,
                                          greedy=greedy)
                    for req, chunk in zip(packed, chunks):
                        req.fed += len(chunk)
                    if type(span) is SpanHandle:
                        self._trace_chunks(packed, self.engine.last_launch,
                                           behind=0)
                with open_span(self.tracer, "sample") as span:
                    emitted = self._sample_and_advance(packed, out, greedy)
                    if type(span) is SpanHandle:
                        span.attrs = {
                            "sampled": len(emitted),
                            "device_sampled": len(emitted) if greedy else 0}
        if decode_tick:
            # per-tick TPOT accounting divides by tokens DELIVERED (a
            # speculative tick can emit several per request)
            now = time.monotonic()
            self.metrics.record_decode_tick(len(emitted), len(packed),
                                            now - t0, now)
        return kind, packed, emitted, t0

    # -- a ragged batch under the program before it -------------------- #
    def _ragged_due(self, step: _InFlight) -> bool:
        """Can the host tell, before it packs, that the tick after ``step``
        may run a ragged batch: something waits to be admitted or resumed,
        or a running request is not among the rows ``step`` hands a token
        (it is mid-prompt)."""
        return bool(self._queued or self._preempted
                    or len(self._running) != len(step.rows))

    def _tick_ahead(self, step: _InFlight):
        """One tick with ``step`` in flight and a ragged batch due after
        it: (a) pack and prepare that batch while ``step`` runs, from the
        view "``step`` has completed": the rows it hands a token decode
        next, on a token that does not exist yet (late rows), unless that
        token ends them by length; mid-prompt rows, resumes and the queue
        are packed as ever, from host tokens; (b) fetch ``step``'s tokens,
        the tick's one wait; (c) check them against the late rows' stops
        and launch the batch on them; (d) hand ``step``'s tokens out,
        under the program just launched.  Returns what ``_tick_level``
        returns, with ``step``'s tokens as the emitted ones.

        None when the view cannot be packed from, before anything of the
        engine's has moved (requests admitted on the way stay admitted):
        the decode set needs a preemption, nothing but decodes came of it
        after all, a packed row is not greedy, or a path that frees a
        sequence settled ``step`` under the packing.  (A program is only
        ever in flight with ``fast_decode`` on and speculation off:
        ``set_speculative_enabled`` settles first.)

        When the view was wrong (a late row's token is one of its stop
        tokens) the prepared batch is discarded, nothing of it having
        reached the device, and after (d) the tick is packed again the
        ordinary way."""
        uids: List[int] = []
        chunks: List[List[int]] = []
        packed: List[Request] = []
        with open_span(self.tracer, "pack") as span:
            late = self._pack_decodes(uids, chunks, packed, after=step)
            held = None
            if late is not None:
                held = self._pack_prefills(uids, chunks, packed)
            if type(span) is SpanHandle:
                span.attrs = self._left_waiting(held)
        if late is None or self._inflight is not step:
            return None
        n_decode = sum(r in late or r.state is RequestState.DECODE
                       for r in packed)
        if n_decode == len(packed) \
                or not all(r.sampling.greedy for r in packed):
            return None
        kind = "mixed" if n_decode else "prefill"
        t0 = self._begin(packed)
        with step_annotation(self._tick):
            with open_span(self.tracer, "prefill") as span:
                prepared = self.engine.prepare(
                    uids, chunks, late=[r.uid for r in late])
                try:
                    toks = self._fetch(step.nxt, step.launch)
                except Exception:
                    self.engine.discard(prepared)
                    self._abandon()
                    raise
                self._inflight = None
                tokens = {r.uid: int(toks[slot]) for r, slot in late.items()}
                wrong = any(r.finish_reason is not None
                            or r.sampling.is_stop_token(tokens[r.uid])
                            for r in late)
                if wrong:
                    self.engine.discard(prepared)
                    self.ragged_discards += 1
                else:
                    self._launch_ragged(span, prepared, packed, chunks,
                                        tokens, behind=step.launch)
            with open_span(self.tracer, "sample") as span:
                emitted = self._advance_step(step, toks, span)
        if wrong:
            again = self._tick_level()
            if again is None:
                return None, packed, emitted, t0
            kind, packed, more, _ = again
            emitted += more
        return kind, packed, emitted, t0

    def _launch_ragged(self, span, prepared, packed, chunks, tokens,
                       behind: int) -> _InFlight:
        """Launch a prepared ragged batch of greedy rows and leave it in
        flight.  ``behind`` is the launch it was prepared under and whose
        tokens it is launched on (0: the chip was empty).  ``span``, the
        ``prefill`` span around it, closes with ``ragged_steps`` (1) and
        ``ragged_ahead`` (was there such a launch)."""
        ahead = int(behind > 0)
        try:
            _, nxt, launch = self.engine.launch(prepared, tokens)
        except Exception:
            self._abandon()
            raise
        slot = {uid: i for i, uid in enumerate(prepared.scheduled)}
        rows = []
        for req, chunk in zip(packed, chunks):
            req.fed += len(chunk)
            if req.uid in tokens or req.remaining_feed == 0:
                rows.append((req, slot[req.uid]))
        self.ragged_ahead_ticks += ahead
        if type(span) is SpanHandle:
            span.attrs = {"ragged_steps": 1, "ragged_ahead": ahead}
            # (a late row is fed the token its prompt's last chunk made)
            self._trace_chunks([r for r in packed if r.uid not in tokens],
                               launch, behind)
        self._inflight = _InFlight(packed, rows, nxt, ahead, launch,
                                   ragged=True)
        return self._inflight

    def _fast_decode_tick(self, uids, chunks, packed) -> List[Tuple[Request,
                                                                    int]]:
        """Steady-state decode tick: one ``decode_step`` dispatch against
        the device-resident block tables.  All-greedy batches fetch only
        the argmax'd token vector (a few bytes/request); any stochastic
        request still needs its logits row on the host for the
        (seed, uid, position)-keyed sampler.

        The greedy tick keeps its tokens on the device until it has given
        the device its next program: the step whose tokens this tick
        returns is the one in flight (dispatched during the tick before)
        or is dispatched now; then, if the next tick will be a pure-decode
        tick too, the step after it is dispatched over the rows that go on
        (``_next_decode_rows``: all of this step's, or those its token does
        not end by length) on this step's device-resident ``nxt``; only
        then are this step's tokens fetched and handed out (``_consume``).
        So the tick in which a row ends by length leaves a program in
        flight all the same, and the arrival that follows the finish is
        packed and built under it (``_tick_ahead``).  The ``decode`` span
        closes with ``steps`` (1), ``ahead`` (was the returned step
        dispatched a tick ago) and ``read_blocks`` (the table blocks that
        step's rows hold up to the positions it fed: what its attention
        read, from the host's own lengths), and under a model with a sparse
        -attention indexer ``idx_keys`` / ``sel_keys`` (the positions that
        step's rows scored, ``p + 1`` each, and read, ``min(p + 1,
        index_topk)``)."""
        self.fast_ticks += 1
        with open_span(self.tracer, "decode") as span:
            traced = type(span) is SpanHandle
            if not all(r.sampling.greedy for r in packed):
                logits = self.engine.decode_step(uids, [c[0] for c in chunks])
                rows = np.asarray(
                    self._fetch(logits, self.engine.last_launch),
                    np.float32)[:len(uids)]
                held = self._held_blocks(packed) if traced else 0
                fed = [r.fed for r in packed] if traced else ()
                for req in packed:
                    req.fed += 1
                tokens_out = sample_batch(
                    rows, [r.sampling for r in packed],
                    [len(r.generated) for r in packed],
                    [r.uid for r in packed])
                emitted = self._advance_emitted(packed, tokens_out.tolist())
                ahead = 0
            else:
                step, self._inflight = self._inflight, None
                try:
                    if step is None:
                        step = self._dispatch_decode(
                            uids, packed, [c[0] for c in chunks], ahead=0)
                    on = self._next_decode_rows(step, packed)
                    if on:
                        rows = [row for _, row in on]
                        if rows == list(range(len(on))):
                            rows = None     # each stays where it stood
                        self._inflight = self._dispatch_decode(
                            [r.uid for r, _ in on], [r for r, _ in on],
                            step.nxt, ahead=1, rows=rows)
                except Exception:
                    self._abandon()
                    raise
                held = self._held_blocks(step.packed) if traced else 0
                fed = [r.fed for r in step.packed] if traced else ()
                emitted = self._consume(step)
                ahead = step.ahead
            if traced:
                span.attrs = {"ahead": ahead, "steps": 1,
                              "read_blocks": held}
                if getattr(self.engine, "index_topk", None) is not None:
                    # what the consumed step's indexer scored and read
                    span.attrs.update(self.engine.index_counters(fed))
        return emitted

    def _held_blocks(self, packed) -> int:
        """Table blocks the rows of a decode step about to be consumed hold
        up to the position it fed (``fed``, before the step is counted)."""
        bs = self.engine.state_manager.block_size
        return sum(r.fed // bs + 1 for r in packed)

    def _dispatch_decode(self, uids, packed, tokens, ahead: int,
                         rows: Optional[List[int]] = None) -> _InFlight:
        """One greedy ``decode_step`` over ``packed``'s rows, fed
        ``tokens``: host ints, or the ``nxt`` of the step before it, where
        ``rows[i]`` is the row of it that holds ``packed[i]``'s token (None:
        row ``i``; the engine does the gather)."""
        _, nxt = self.engine.decode_step(uids, tokens, greedy=True,
                                         rows=rows)
        return _InFlight(packed, list(zip(packed, range(len(packed)))), nxt,
                         ahead, self.engine.last_launch)

    def _rows_going_on(self, step: _InFlight) -> List[Tuple[Request, int]]:
        """The rows of ``step``, a program in flight, that decode next on
        the token it hands them, each with its row of ``step.nxt``: every
        row it hands a token whose request has not ended while it was in
        flight (a stop token a tick ago, a failure) and does not end by
        length with that token (``max_new_tokens`` or ``max_context``
        reached with it).  All the host can tell before the token exists: a
        stop token it cannot, and that row is dropped when the step sent
        ahead over it is consumed (``_advance_step``) or the batch prepared
        on it discarded (``_tick_ahead``).  The one rule of both ways of
        running ahead: the decode step dispatched behind a decode step
        (``_next_decode_rows``) and the late rows of a ragged batch prepared
        under a program (``_pack_decodes``)."""
        on = []
        for r, row in step.rows:
            n = len(r.generated) + 1
            if r.finish_reason is None \
                    and n < r.sampling.max_new_tokens \
                    and len(r.prompt) + n < self.max_context:
                on.append((r, row))
        return on

    def _next_decode_rows(self, step: _InFlight,
                          packed) -> List[Tuple[Request, int]]:
        """The rows to dispatch the decode step after ``step`` over, before
        ``step``'s tokens arrive: its rows that go on (``_rows_going_on``),
        when the host can tell that the next tick will be a greedy
        pure-decode tick over exactly those; else none.  Read from the
        host's own state: no speculation (its verify pass packs the tick),
        nothing waiting to join (queue, preempted, a running request that
        is not among ``packed``, the live rows of ``step``: it is
        mid-prefill), no row that goes on whose deadline falls due within
        a tick, and KV room for each one's next position without a
        preemption (reckoned with the blocks of the rows that end still
        held: they are freed when ``step`` is consumed)."""
        if (self._spec_active is not None or self._queued
                or self._preempted or len(self._running) != len(packed)):
            return []
        on = self._rows_going_on(step)
        due = time.monotonic() + self._tick_s
        if any(r.deadline_s is not None
               and due - r.arrival_time > r.deadline_s for r, _ in on):
            return []
        if on and self.engine.can_schedule([r.uid for r, _ in on],
                                           [1] * len(on)):
            return on
        return []

    def _consume(self, step: _InFlight) -> List[Tuple[Request, int]]:
        """Fetch ``step``'s tokens and hand each to its request, as the
        tick that owns it does."""
        return self._advance_step(step, self._fetch_step(step))

    def _fetch_step(self, step: _InFlight) -> np.ndarray:
        """The wait for ``step``'s tokens; a failed one abandons it."""
        try:
            return self._fetch(step.nxt, step.launch)
        except Exception:
            self._abandon()
            raise

    def _advance_step(self, step: _InFlight, toks,
                      span=None) -> List[Tuple[Request, int]]:
        """Hand the fetched tokens of ``step`` to its rows.  A row whose
        request ended while the step was in flight (a stop token a tick
        ago, a failure) is dropped: nothing past the end is emitted or
        recorded.  The rows that go on tell the engine the values a decode
        step dispatched behind this one was fed from the device, for the
        prefix cache (a ragged step launched behind it was fed them from
        the host).  ``span``, the ``sample`` span of a tick that launched
        a ragged batch, closes with the rows that emitted, all of them the
        program's argmax."""
        rows = [(r, int(toks[i])) for r, i in step.rows
                if r.finish_reason is None]
        if not step.ragged:
            for req, _ in rows:
                req.fed += 1
        emitted = self._advance_emitted([r for r, _ in rows],
                                        [t for _, t in rows])
        if type(span) is SpanHandle:
            span.attrs = {"sampled": len(emitted),
                          "device_sampled": len(emitted)}
        if self._inflight is not None and not self._inflight.ragged:
            fed = [(r.uid, t) for r, t in rows if r.finish_reason is None]
            if fed:
                self.engine.record_device_tokens(*zip(*fed))
            else:
                self._inflight = None   # every row stopped: none is wanted
        return emitted

    def _settle(self) -> None:
        """Bring the host level with the device: fetch and hand out the
        tokens of the program in flight, if there is one, whatever its
        kind.  Every path that frees or moves a sequence outside the tick
        that returns those tokens calls this first, so it never meets an
        engine position the requests have not reached; the next ``step``
        returns the tokens.  So does a tick with a ragged step in flight
        and no ragged batch due after it (a pure-decode tick follows,
        which needs the tokens on the host), or none it can prepare from
        the view "the step has completed".  The wait and the advance lie
        under a ``retire`` span, on the scheduler's own trace wherever
        the call came from."""
        step, self._inflight = self._inflight, None
        if step is not None:
            with open_span(self.tracer, "retire",
                           trace_id=self.sched_trace_id, tid=self.trace_tid):
                self._early.extend(self._consume(step))

    def _abandon(self) -> None:
        """A program in flight failed at its dispatch or at its fetch: what
        it and anything dispatched behind it left in the pool and in the
        engine's positions is not what the requests were handed.  Drop the
        state in flight, let the engine recover its donated cache, and
        send every running request back through recompute."""
        self._inflight = None
        self.engine._recover_donated_cache()
        for req in list(self._running.values()):
            self._preempt(req)

    def _fetch(self, device_array, launch: int) -> np.ndarray:
        """The tick's one blocking transfer: the host waits here for the
        step program to finish and its tokens (or logits) to arrive.  The
        span closes with ``launch``, the engine's number of the launch
        whose result this is, and, where the array is a step's
        ``next_tokens`` (a vector; logits and a verify step's candidates
        are matrices) of a model with ``step_counters``, with what the
        model counted on the device in that step, which came behind the
        tokens (``engine.counters_of``: e.g. ``moe_slots`` /
        ``moe_zero_slots`` / ``moe_held_rows``)."""
        import jax

        with open_span(self.tracer, "fetch") as span:
            out = np.asarray(jax.device_get(device_array))
            if type(span) is SpanHandle:
                span.attrs = {"launch": launch}
                if out.ndim == 1 and getattr(self.engine, "step_counters",
                                             ()):
                    span.attrs.update(self.engine.counters_of(out))
            return out

    # -- speculative decode -------------------------------------------- #
    def _speculative_decode_tick(self, uids, chunks, packed
                                 ) -> Optional[List[Tuple[Request, int]]]:
        """Draft + one multi-token verify pass over the decode batch.

        Returns the emitted ``(request, token)`` pairs, or None when
        speculation opted out this tick (no drafts anywhere, or no room
        for the K-token lookahead) — the caller then runs the plain fast
        decode tick.  Output is token-for-token what sequential decode
        would emit: acceptance reuses the (seed, uid, position)-keyed
        sampler against each candidate slot's logits, and a stop
        token / length limit inside an accepted run truncates exactly
        where the sequential run would have stopped.
        """
        spec = self.speculative
        drafts: List[List[int]] = []
        k_targets: List[int] = []
        for r in packed:
            # acceptance-aware K: a request whose accept-rate EWMA has
            # decayed drafts fewer tokens (down to min_draft_k), so the
            # verify pass stops paying lookahead it never cashes;
            # draft_k is the cap, so program shapes stay bounded
            k_r = (self._spec_k.get(r.uid, spec.draft_k)
                   if spec.autotune_k else spec.draft_k)
            if self.spec_k_cap is not None:
                k_r = max(1, min(k_r, self.spec_k_cap))
            k_targets.append(k_r)
            # never draft past the generation budget: at most
            # remaining - 1 drafts can be emitted alongside the bonus
            remaining = r.sampling.max_new_tokens - len(r.generated)
            drafts.append(list(
                spec.drafter.draft(r.history, min(k_r, remaining - 1))
            )[:k_r])
        if not any(drafts):
            return None
        # the pass's K covers the longest draft actually proposed — an
        # all-shrunk batch runs a genuinely smaller verify program
        gamma = (max(len(d) for d in drafts)
                 if spec.autotune_k or self.spec_k_cap is not None
                 else spec.draft_k)
        K = gamma + 1
        if not self.engine.can_allocate(uids, [K] * len(uids)):
            return None                  # lookahead KV/context won't fit
        feed = [[r.history[-1]] + d + [0] * (gamma - len(d))
                for r, d in zip(packed, drafts)]
        spans = [len(d) + 1 for d in drafts]
        if all(r.sampling.greedy for r in packed):
            # all-greedy: the step program argmax'd every candidate slot
            # on device — fetch K ints per sequence, never the [n, K,
            # vocab] logits (the same asymmetry the plain greedy fast
            # tick exploits via decode_step(greedy=True))
            _, nxt = self.engine.verify_step(uids, feed, greedy=True)
            toks = self._fetch(nxt, self.engine.last_launch)[:len(uids)]
            cand = np.concatenate(
                [toks[i, :m] for i, m in enumerate(spans)])
        else:
            # device logits [max_seqs, K, vocab]; the stochastic sampler
            # needs them on host — one fetch per verify pass (vs one per
            # token unspeculated).  One vectorised sampler call over
            # every candidate slot: slot k of request i draws at
            # generation position len(generated)+k — the exact key
            # sequential decode would use
            logits = self.engine.verify_step(uids, feed)
            rows = np.asarray(self._fetch(logits, self.engine.last_launch),
                              np.float32)[:len(uids)]
            flat_rows, flat_params, flat_pos, flat_uids = [], [], [], []
            for i, (r, d) in enumerate(zip(packed, drafts)):
                m = spans[i]
                flat_rows.append(rows[i, :m])
                flat_params.extend([r.sampling] * m)
                flat_pos.extend(len(r.generated) + k for k in range(m))
                flat_uids.extend([r.uid] * m)
            cand = sample_batch(np.concatenate(flat_rows, axis=0),
                                flat_params, flat_pos, flat_uids)
        with open_span(self.tracer, "advance"):
            emitted: List[Tuple[Request, int]] = []
            now = time.monotonic()
            self.spec_stats.ticks += 1
            off = 0
            for i, (req, d) in enumerate(zip(packed, drafts)):
                out, acc = accept_drafts(cand[off:off + spans[i]], d)
                off += spans[i]
                self.spec_stats.drafted += len(d)
                self.spec_stats.accepted += acc
                self.spec_stats.k_sum += k_targets[i]
                self.spec_stats.k_requests += 1
                if spec.autotune_k and d:
                    a = spec.accept_ewma_alpha
                    rate = acc / len(d)
                    prev = self._spec_accept_ewma.get(req.uid)
                    ew = rate if prev is None else (1.0 - a) * prev + a * rate
                    self._spec_accept_ewma[req.uid] = ew
                    k_cur = k_targets[i]
                    if ew < spec.shrink_threshold and k_cur > spec.min_draft_k:
                        k_cur -= 1
                    elif ew > spec.grow_threshold and k_cur < spec.draft_k:
                        k_cur += 1
                    self._spec_k[req.uid] = k_cur
                # commit the accepted feed prefix (input + accepted drafts);
                # the engine trims rejected lookahead blocks back
                self.engine.commit_verified(req.uid, feed[i][:1 + acc])
                req.fed += 1 + acc
                got = self._emit_many(req, out, now)
                # count what was DELIVERED, not what was accepted — a stop
                # token mid-burst truncates delivery exactly where
                # sequential decode would have stopped
                self.spec_stats.emitted += len(got)
                emitted.extend(got)
        return emitted

    def _emit_many(self, req: Request, tokens: Sequence[int],
                   now: float) -> List[Tuple[Request, int]]:
        """Emit a verify pass's accepted burst, stopping exactly where
        sequential decode would (stop token / max_new_tokens /
        max_context truncate the burst)."""
        emitted: List[Tuple[Request, int]] = []
        for tok in tokens:
            req.emit(int(tok), now)
            emitted.append((req, int(tok)))
            reason = req.should_stop()
            if reason is None and len(req.history) >= self.max_context:
                reason = "length"
            if reason is not None:
                self._finish(req, reason)
                break
        return emitted

    # -- packing ------------------------------------------------------- #
    def _pack_decodes(self, uids, chunks, packed,
                      after: Optional[_InFlight] = None):
        """All running decode sequences, one token each; preempt under KV
        pressure until the set fits.

        With ``after``, a program in flight, the set is that of the tick
        after it: the rows it hands a token decode on that token, which
        does not exist yet (late rows: a placeholder is packed), unless it
        ends them by length (``_rows_going_on``).  Returns
        ``{late request: its row of after.nxt}``, or None when that set
        does not fit: a preemption settles the program in flight first, so
        it is the ordinary tick's to make."""
        late: Dict[Request, int] = {}
        in_step = ()
        if after is not None:
            in_step = {r for r, _ in after.rows}
            late = dict(self._rows_going_on(after))
        decodes = sorted(
            [r for r in self._running.values()
             if r.remaining_feed == 1 and r not in in_step] + list(late),
            key=lambda r: r.admitted_at)
        while decodes:
            cand_uids = [r.uid for r in decodes]
            if self.engine.can_schedule(cand_uids, [1] * len(cand_uids)):
                break
            if after is not None:
                return None
            victim = self._pick_victim()
            self._preempt(victim)
            decodes = [r for r in decodes if r.uid != victim.uid]
        for r in decodes:
            uids.append(r.uid)
            chunks.append([0] if r in late else [r.history[-1]])
            packed.append(r)
        return late

    def _pack_prefills(self, uids, chunks, packed) -> Optional[str]:
        """SplitFuse: fill the remaining budget with prefill chunks —
        running mid-prefill first, then preempted resumes, then new
        admissions (priority, then FIFO).  Returns the first rule that
        held a request back from admission, for the ``pack`` span:
        ``budget`` (the tick's tokens are spent), ``rows`` (the batch has
        ``max_seqs`` rows), ``slots`` (the running set is full) or ``kv``
        (no chunk of it fits the pool beside the packed set); None when
        everything waiting was admitted."""
        held = None
        budget_left = self.token_budget - sum(len(c) for c in chunks)
        mid = sorted((r for r in self._running.values()
                      if r.remaining_feed > 1 and r not in packed),
                     key=lambda r: r.admitted_at)
        resumes = sorted(self._preempted,
                         key=lambda r: (-r.priority, r.arrival_time))
        fresh = sorted(self._queued,
                       key=lambda r: (-r.priority, r.arrival_time))
        for req in itertools.chain(mid, resumes, fresh):
            if budget_left <= 0 or len(uids) >= self.max_seqs:
                held = held or ("budget" if budget_left <= 0 else "rows")
                break
            admitting = req.state in (RequestState.QUEUED,
                                      RequestState.PREEMPTED)
            if admitting and len(self._running) + 1 > self.max_seqs:
                held = held or "slots"
                continue   # running set must stay one-forward-sized
            want = min(req.remaining_feed, budget_left,
                       self.max_context - req.fed)
            chunk = self._max_feasible_chunk(uids, chunks, req.uid, want)
            if chunk <= 0:
                if admitting:
                    held = held or "kv"
                    break  # KV full: later (lower-priority) queue entries
                           # can't fit either — don't starve order
                continue
            if admitting:
                self._admit(req)
                # prefix-cache attach: (re)admission skips the prefill of
                # any cached span — including a preempted request's own
                # still-warm history, making recompute-resume nearly free
                if hasattr(self.engine, "attach_prefix"):
                    stats = getattr(self.engine, "prefix_cache_stats", None)
                    snap = (None if stats is None else
                            stats.attach_snapshot())
                    hit = self.engine.attach_prefix(req.uid, req.history)
                    if hit:
                        req.fed = hit
                        chunk = min(chunk, req.remaining_feed)
                        # attaching pinned warm blocks that can_schedule
                        # counted as evictable when the already-packed
                        # chunks were validated — re-check the whole set
                        # and defer this request if it no longer fits
                        lens = [len(c) for c in chunks]
                        if not self.engine.can_schedule(
                                uids + [req.uid], lens + [chunk]):
                            # the discarded attach saved nothing — its
                            # prefill skip never ran, and the retry next
                            # tick records the lookup/hit/fork again
                            # (evicted_blocks stays: those frees happened)
                            if snap is not None:
                                stats.restore_attach(snap)
                            self._preempt(req)
                            held = held or "kv"
                            break
            hist = req.history
            uids.append(req.uid)
            chunks.append(hist[req.fed:req.fed + chunk])
            packed.append(req)
            budget_left -= chunk
        return held

    def _max_feasible_chunk(self, uids, chunks, uid: int, want: int) -> int:
        """Largest chunk <= want that ``can_schedule`` accepts alongside
        the already-packed set (binary search: feasibility is monotone)."""
        if want <= 0:
            return 0
        lens = [len(c) for c in chunks]
        lo, hi = 0, want
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if self.engine.can_schedule(uids + [uid], lens + [mid]):
                lo = mid
            else:
                hi = mid - 1
        return lo

    # -- admission / preemption ---------------------------------------- #
    def _admit(self, req: Request) -> None:
        if req.state is RequestState.QUEUED:
            self._queued.remove(req)
        else:
            self._preempted.remove(req)
        self._parked_backlog -= self._work(req)
        req.transition(RequestState.PREFILL)
        req.admitted_at = next(self._admit_counter)
        self._running[req.uid] = req
        self._open_req_span(req, "prefill")

    def _pick_victim(self) -> Request:
        """Lowest priority, then most recently admitted."""
        if not self._running:
            raise RuntimeError("no running request to preempt")
        return min(self._running.values(),
                   key=lambda r: (r.priority, -r.admitted_at))

    def _preempt(self, req: Request) -> None:
        self._settle()
        if self.engine.state_manager.get_sequence(req.uid) is not None:
            self.engine.flush_to_host([req.uid])
        del self._running[req.uid]
        req.fed = 0
        req.preemptions += 1
        self._close_req_span(req, outcome="preempted")
        self._open_req_span(req, "queued")      # it waits again
        req.transition(RequestState.PREEMPTED)
        self._preempted.append(req)
        self._parked_backlog += self._work(req)
        self.metrics.record_preemption(req)
        logger.debug(f"serving: preempted request {req.uid} "
                     f"({len(req.generated)} tokens generated)")

    def _fail(self, req: Request, reason: str) -> None:
        # its row of a step in flight is dropped, not handed out
        req.finish_reason = reason
        self._settle()
        # a QUEUED request can hold engine state too: resubmit() with a KV
        # payload injects the sequence before admission packs it
        if self.engine.state_manager.get_sequence(req.uid) is not None:
            self.engine.flush([req.uid])
        if req.uid in self._running:
            del self._running[req.uid]
        if req in self._queued:
            self._queued.remove(req)
            self._parked_backlog -= self._work(req)
        if req in self._preempted:
            self._preempted.remove(req)
            self._parked_backlog -= self._work(req)
        self._close_req_span(req, outcome="failed", reason=reason)
        req.transition(RequestState.FAILED)
        self._drop_request_state(req.uid)
        self._finished.append(req)
        self.metrics.record_finish(req)
        logger.warning(f"serving: request {req.uid} failed: {reason}")

    def _expire_deadlines(self) -> None:
        """Fail every non-terminal request past its ``deadline_s`` (reason
        "deadline") — queued, running, or preempted alike.  Tokens already
        generated stay on the request, but a blown SLO is a failure: the
        client stopped waiting, so finishing the work is wasted compute."""
        late = [req for req in [*self._queued, *self._running.values(),
                                *self._preempted] if req.past_deadline]
        for req in late:        # before the first _fail settles
            req.finish_reason = "deadline"
        for req in late:
            self._fail(req, "deadline")

    def _reap_unservable(self) -> None:
        """Terminate requests whose token history has outgrown the ENTIRE
        KV pool: they can never feed again, alone or otherwise.  Without
        this guard a decode at the pool boundary enters an infinite
        preempt -> recompute -> preempt cycle.  Generated tokens are kept
        (FINISHED, truncated by capacity); a request that never produced
        a token fails instead."""
        sm = self.engine.state_manager
        usable = sm.allocator.num_blocks - 1          # trash block reserved
        for req in [*self._running.values(), *self._preempted]:
            if -(-len(req.history) // sm.block_size) <= usable:
                continue
            self._settle()
            if req.uid in self._running:
                self.engine.flush([req.uid])
                del self._running[req.uid]
            else:
                self._preempted.remove(req)
                self._parked_backlog -= self._work(req)
            if req.generated:
                req.finish_reason = "length"
                self._close_req_span(req, outcome="finished",
                                     reason="length")
                req.transition(RequestState.FINISHED)
            else:
                req.finish_reason = "kv_capacity"
                self._close_req_span(req, outcome="failed",
                                     reason="kv_capacity")
                req.transition(RequestState.FAILED)
            self._drop_request_state(req.uid)
            self._finished.append(req)
            self.metrics.record_finish(req)
            logger.warning(
                f"serving: request {req.uid} truncated — history of "
                f"{len(req.history)} tokens exceeds the {usable}-block "
                f"KV pool")

    def _handle_stall(self) -> None:
        """Nothing could be packed.  With two or more running requests
        this is a recoverable mid-prefill deadlock (they jointly hold the
        pool, none can extend): preempt one — its blocks let the others
        finish, and it resumes by recompute.  A SINGLE stalled holder (or
        a stall with nothing running) can never fit and is failed rather
        than spun on; _reap_unservable catches the history-outgrew-pool
        case before it reaches here."""
        if len(self._running) > 1:
            self._preempt(self._pick_victim())
        elif self._running:
            self._fail(self._pick_victim(), "kv_capacity")
        elif self._preempted:
            self._fail(self._preempted[0], "kv_capacity")
        elif self._queued:
            self._fail(self._queued[0], "kv_capacity")

    # -- sampling / lifecycle advance ---------------------------------- #
    def _sample_and_advance(self, packed, out,
                            greedy: bool) -> List[Tuple[Request, int]]:
        """Hand a token to every row of a ``put`` tick whose feed is
        complete.  ``out`` is what the engine returned per uid: with
        ``greedy`` the tokens themselves (the step program's argmax),
        otherwise the logits rows, sampled here."""
        ready = [r for r in packed if r.remaining_feed == 0]
        if not ready:
            return []
        if greedy:
            return self._advance_emitted(ready, [out[r.uid] for r in ready])
        rows = np.stack([np.asarray(out[r.uid], np.float32)
                         for r in ready])
        tokens = sample_batch(rows, [r.sampling for r in ready],
                              [len(r.generated) for r in ready],
                              [r.uid for r in ready])
        return self._advance_emitted(ready, tokens.tolist())

    def _advance_emitted(self, ready,
                         tokens: List[int]) -> List[Tuple[Request, int]]:
        """Hand each request its token (``on_token`` runs here), check its
        stops, move its lifecycle on."""
        now = time.monotonic()
        emitted: List[Tuple[Request, int]] = []
        with open_span(self.tracer, "advance"):
            for req, tok in zip(ready, tokens):
                req.emit(tok, now)
                emitted.append((req, tok))
                reason = req.should_stop()
                if reason is None and len(req.history) >= self.max_context:
                    reason = "length"
                if reason is not None:
                    self._finish(req, reason)
                elif req.state is RequestState.PREFILL:
                    req.transition(RequestState.DECODE)
                    # prefill phase over: the span chain continues as
                    # decode
                    self._open_req_span(req, "decode")
        return emitted

    def _drop_request_state(self, uid: int) -> None:
        """Terminal-transition bookkeeping shared by finish/fail/reap/
        handoff: the uid leaves the live set and its speculative
        autotune state (accept-rate EWMA + effective K) is dropped so
        the tables stay bounded by the live request set."""
        self._live_uids.discard(uid)
        self._spec_accept_ewma.pop(uid, None)
        self._spec_k.pop(uid, None)

    def _finish(self, req: Request, reason: str) -> None:
        """Only ever called while tokens are handed out.  A request that
        stops on a stop token may hold a row of the step dispatched ahead:
        that row is dropped when the step is consumed, and its write into
        the blocks freed here lands before any later program's (each
        depends on the donated cache)."""
        self.engine.flush([req.uid])
        del self._running[req.uid]
        req.finish_reason = reason
        self._close_req_span(req, outcome="finished", reason=reason)
        req.transition(RequestState.FINISHED)
        self._drop_request_state(req.uid)
        self._finished.append(req)
        self.metrics.record_finish(req)

    # ------------------------------------------------------------------ #
    # Driving loops
    # ------------------------------------------------------------------ #
    def telemetry(self, _snapshot: Optional[Dict[str, float]] = None
                  ) -> Dict[str, float]:
        """Every ``serving/*`` scalar this scheduler emits, fully
        namespaced — the SLO snapshot plus prefix-cache and fast-tick
        telemetry.  This is both ``_export_metrics``'s source and the
        provider a unified :class:`MetricsRegistry` snapshots."""
        if _snapshot is None:
            _snapshot = self.metrics.snapshot()
        out = {f"serving/{k}": float(v) for k, v in _snapshot.items()}
        out["serving/fast_decode_ticks"] = float(self.fast_ticks)
        out["serving/ragged_ahead_ticks"] = float(self.ragged_ahead_ticks)
        out["serving/ragged_discards"] = float(self.ragged_discards)
        if self.speculative is not None:
            out.update((f"serving/spec_{k}", float(v))
                       for k, v in self.spec_stats.as_dict().items())
        pc = getattr(self.engine.state_manager, "prefix_cache", None) \
            if hasattr(self.engine, "state_manager") else None
        if pc is not None:
            out.update((f"serving/prefix_{k}", float(v))
                       for k, v in pc.stats.as_dict().items())
        return out

    def _export_metrics(self) -> None:
        """serving/* scalars plus prefix-cache and fast-tick telemetry.
        ONE metrics snapshot feeds both the base-name set and the extra
        list (snapshot percentiles are not free on the export path)."""
        snap = self.metrics.snapshot()
        base = {f"serving/{k}" for k in snap}
        extra = [(k, v) for k, v in self.telemetry(snap).items()
                 if k not in base]
        self.metrics.export(extra=extra, snapshot=snap)

    def run_until_idle(self, max_ticks: Optional[int] = None) -> List[Request]:
        """Step until every submitted request reaches a terminal state
        (or ``max_ticks``).  Returns all finished/failed requests so far."""
        ticks = 0
        while self.num_pending:
            if max_ticks is not None and ticks >= max_ticks:
                break
            self.step()
            ticks += 1
        self._settle()      # max_ticks can stop the loop a step ahead
        self._export_metrics()
        return self.finished_requests

    def run_with_arrivals(self, prompts, arrivals, sampling=None,
                          priority: int = 0,
                          poll_s: float = 0.005) -> List[Request]:
        """Open-loop arrival driver: submit ``prompts[i]`` once
        ``arrivals[i]`` seconds of wall clock have elapsed, stepping the
        scheduler between arrivals until everything terminates.  Used by
        the tier-1 smoke.  ``sampling`` is one :class:`SamplingParams` shared
        by all requests, or a per-request sequence."""
        n = len(prompts)
        per_req = isinstance(sampling, (list, tuple))
        reqs: List[Request] = []
        t0 = time.monotonic()
        while len(reqs) < n or self.num_pending:
            now = time.monotonic() - t0
            while len(reqs) < n and arrivals[len(reqs)] <= now:
                i = len(reqs)
                reqs.append(self.submit(
                    prompts[i],
                    sampling=sampling[i] if per_req else sampling,
                    priority=priority))
            if self.num_pending:
                self.step()
            elif len(reqs) < n:
                time.sleep(min(arrivals[len(reqs)] - now, poll_s))
        return reqs

    def shutdown(self, drain_deadline: float = 30.0, handoff: bool = False):
        """Graceful shutdown: close admission immediately (``submit``
        raises from now on) and let in-flight work finish via
        :meth:`drain`.

        ``handoff=False`` (the default): whatever is still pending after
        ``drain_deadline`` seconds is failed with reason ``"shutdown"``
        (counted in ``serving/shutdown_failed``).  Returns True when
        everything drained — nothing was dropped.

        ``handoff=True`` (rolling restarts / elastic downsize): pending
        requests are DETACHED instead of failed — each becomes a
        serializable :class:`~deepspeed_tpu.serving.request.RequestSnapshot`
        (tokens emitted, sampler seed, tenant/priority/remaining deadline)
        that another replica's :meth:`resubmit` continues token-exactly.
        Returns ``(drained, snapshots)``; ``snapshots`` is empty when the
        drain completed in time."""
        self._shutting_down = True
        idle = self.drain(drain_deadline)
        if handoff:
            snaps = []
            if not idle:
                leftovers = [*self._queued, *list(self._running.values()),
                             *self._preempted]
                logger.info(
                    f"serving: shutdown drain deadline ({drain_deadline}s) "
                    f"expired — handing off {len(leftovers)} request(s)")
                snaps = [self._detach(req)[0] for req in leftovers]
                self._export_metrics()
            return idle, snaps
        if not idle:
            leftovers = [*self._queued, *list(self._running.values()),
                         *self._preempted]
            logger.warning(
                f"serving: shutdown drain deadline ({drain_deadline}s) "
                f"expired with {len(leftovers)} request(s) pending — "
                "failing them with reason 'shutdown'")
            for req in leftovers:
                self._fail(req, "shutdown")
            self._export_metrics()
        return idle

    def close_admission(self) -> None:
        """Close admission WITHOUT draining: ``submit`` raises from now
        on and routers skip this replica, but in-flight work keeps
        stepping under the caller's control.  The fleet's graceful
        scale-down uses this to quiesce a victim while it keeps pumping
        the victim's scheduler (and chaos-injecting its drain) itself,
        then calls :meth:`shutdown(0, handoff=True)` to detach whatever
        is left."""
        self._shutting_down = True

    # ------------------------------------------------------------------ #
    # Cross-replica handoff (the fleet layer's migration primitive)
    # ------------------------------------------------------------------ #
    @property
    def accepting_submissions(self) -> bool:
        """False once :meth:`shutdown` closed admission (a router skips
        draining replicas)."""
        return not self._shutting_down

    def _detach(self, req: Request, include_kv: bool = False):
        """Remove ``req`` from every scheduler structure and return
        ``(snapshot, kv_state)`` — the request continues elsewhere as a
        NEW object; this one transitions to the terminal ``HANDED_OFF``
        (so tenant-quota views prune it, and a holder sees it is gone).
        ``include_kv=True`` (running requests only) carries the device KV
        along so the target replica skips the recompute re-prefill.  Both
        callers have settled the step in flight (``drain``'s exit,
        ``extract_for_handoff``)."""
        kv_state = None
        fed = 0
        if req.uid in self._running:
            if include_kv and hasattr(self.engine, "flush_to_host"):
                kv_state = self.engine.flush_to_host(
                    [req.uid], include_kv=True)[req.uid]
                fed = kv_state["seen_tokens"]
            else:
                self.engine.flush_to_host([req.uid])
            del self._running[req.uid]
            req.fed = 0
        elif self.engine.state_manager.get_sequence(req.uid) is not None:
            # an injected-KV request still queued: release its blocks
            self.engine.flush([req.uid])
        if req in self._queued:
            self._queued.remove(req)
            self._parked_backlog -= self._work(req)
        elif req in self._preempted:
            self._preempted.remove(req)
            self._parked_backlog -= self._work(req)
        self._drop_request_state(req.uid)
        snap = req.snapshot(fed_tokens=fed)
        req.finish_reason = "handoff"
        self._close_req_span(req, outcome="handoff")
        tr = self.tracer
        if tr is not None and tr.enabled:
            tr.instant("request/handoff", trace_id=req.trace_id,
                       tid=self.trace_tid,
                       attrs={"kv": kv_state is not None})
        req.transition(RequestState.HANDED_OFF)
        self.metrics.record_handoff(req)
        return snap, kv_state

    def extract_for_handoff(self, uid: int, include_kv: bool = False):
        """Detach one live request for migration to another replica.
        Returns ``(snapshot, kv_state)``; ``kv_state`` is the
        ``flush_to_host(include_kv=True)`` payload when requested and the
        request was running (None otherwise).  The disaggregated
        prefill→decode pump calls this the tick a prefill completes."""
        self._settle()      # before the lookup: its token may end ``uid``
        for req in [*self._running.values(), *self._queued,
                    *self._preempted]:
            if req.uid == uid:
                return self._detach(req, include_kv=include_kv)
        raise ValueError(f"extract_for_handoff: uid {uid} is not live")

    def resubmit(self, snap, kv_state=None, on_token=None) -> Request:
        """Continue a handed-off request on THIS replica.

        Reconstructs a :class:`Request` from ``snap`` (uid preserved — it
        keys the sampling noise stream) and submits it.  Without
        ``kv_state`` the request re-prefills ``prompt + generated``
        (recompute, warm prefix blocks re-attach via the radix cache when
        enabled).  With ``kv_state`` the carried KV is injected through
        ``engine.resume(..., kv_state=...)`` so only the unfed tail is
        ever recomputed; when the KV no longer fits this replica's pool
        the payload is dropped and the request falls back to recompute —
        a handoff may get slower, never lost."""
        req = snap.to_request(on_token=on_token)
        injected = False
        if kv_state is not None and hasattr(self.engine, "resume"):
            sm = self.engine.state_manager
            seen = min(int(kv_state["seen_tokens"]), len(req.history) - 1)
            need = -(-seen // sm.block_size) if seen > 0 else 0
            if seen > 0 and need <= sm.free_blocks \
                    and sm.get_sequence(req.uid) is None \
                    and not self._shutting_down:
                self.engine.resume(req.uid, req.history[:seen],
                                   kv_state=kv_state)
                req.fed = seen
                injected = True
        try:
            return self.submit(request=req)
        except Exception:
            if injected:
                self.engine.flush([req.uid])
            raise

    def drain(self, deadline: float) -> bool:
        """Async-friendly bounded drain: step until idle or ``deadline``
        seconds of wall clock elapse, then return control to the caller
        (an event loop can interleave submits between drains).  Returns
        True when fully idle."""
        end = time.monotonic() + deadline
        while self.num_pending and time.monotonic() < end:
            self.step()
        self._settle()      # the deadline can stop the loop a step ahead
        if not self.num_pending:
            self._export_metrics()
        return self.num_pending == 0
