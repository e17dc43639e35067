"""Process-separated serving replicas under real supervision.

:class:`~deepspeed_tpu.fleet.fleet.ServingFleet` composes replicas
in-process (one engine per replica, one python process) — the right shape
for tests, benches, and single-host serving.  This module is the same
fleet contract across PROCESS boundaries, so a replica can actually be
SIGKILLed, OOM-killed, or wedged and the system provably recovers:

* each replica is a **worker subprocess** (:func:`run_replica_worker`)
  driving its own ``ContinuousBatchScheduler``; it consumes request
  snapshots from a spool-directory inbox and appends every emitted token
  to an ``events.jsonl`` journal (crash-durable: what was flushed is
  recovered, what wasn't is deterministically regenerated on replay);
* each worker runs under its own
  :class:`~deepspeed_tpu.resilience.supervisor.JobSupervisor` — ONE
  supervisor per replica, so a crash or hang restarts that replica alone
  (the whole-group teardown a training job wants is exactly wrong for a
  serving fleet).  The scheduler ticks the supervisor's heartbeat file
  every step (``Heartbeat.from_env``), so a wedged engine forward reads
  as a hang, gets a SIGUSR1 stack dump, and is killed and respawned;
* the :class:`FleetFrontEnd` (parent process) journals every request —
  prompt, sampling seed, every token read back — routes by load, watches
  the supervisors, and on a replica's death/restart replays that
  replica's in-flight requests from the journal: the replay snapshot
  carries the delivered tokens as its ``generated`` prefix, so the
  ``(seed, uid, position)``-keyed sampler continues the exact stream.
  A killed replica loses ZERO requests — and a request that KEEPS
  killing replicas is not replayed forever: every worker death journals
  its in-flight set into a
  :class:`~deepspeed_tpu.fleet.defense.CrashBlame` tracker, repeat
  co-occurrers are replayed **alone** on the respawned worker
  (isolation — no new traffic routes there), and a conviction
  terminalizes the request ``failed reason="quarantined"`` with a
  tenant-visible error.  ``max_replays`` bounds even unconvicted
  replays (``reason="replay_budget"``).

The IPC is deliberately files-only (atomic-rename inbox, append-only
event journal, mtime heartbeats) — the same crash-survivable primitives
the checkpoint and heartbeat layers already trust, with no sockets to
leak or deadlock.
"""

from __future__ import annotations

import itertools
import json
import os
import time
from typing import Callable, Dict, List, Optional

from deepspeed_tpu.fleet.defense import CrashBlame
from deepspeed_tpu.fleet.fleet import FleetRequest
from deepspeed_tpu.observability.flight_recorder import (FlightRecorder,
                                                         write_postmortem)
from deepspeed_tpu.observability.tracer import Tracer, mint_trace_id
from deepspeed_tpu.resilience import heartbeat as hb
from deepspeed_tpu.resilience.supervisor import (BackoffPolicy,
                                                 JobSupervisor, WorkerSpec)
from deepspeed_tpu.serving.request import RequestSnapshot, SamplingParams
from deepspeed_tpu.serving.router import DEFAULT_PRIORITY_CLASSES
from deepspeed_tpu.utils.logging import logger

STOP_FILE = "stop"
INBOX_DIR = "inbox"
#: exported by FleetFrontEnd per launch: each worker incarnation appends
#: to its OWN event journal (``events.<attempt>.jsonl``), so a SIGKILL's
#: torn tail line can never interleave with the respawn's first events
ENV_INCARNATION = "DS_FLEET_INCARNATION"


def events_path(spool_dir: str, attempt: int) -> str:
    return os.path.join(spool_dir, f"events.{attempt}.jsonl")


def flight_path(spool_dir: str, attempt: int) -> str:
    """The worker incarnation's flight-recorder file: its span ring,
    flushed periodically (atomic rename) so a SIGKILL loses at most the
    last ``flush_every`` ticks of spans, never the whole black box."""
    return os.path.join(spool_dir, f"flight.{attempt}.json")


# --------------------------------------------------------------------- #
# Worker side
# --------------------------------------------------------------------- #
def run_replica_worker(spool_dir: str, scheduler,
                       poll_s: float = 0.005,
                       drain_deadline_s: float = 30.0,
                       flight_flush_every: int = 16) -> int:
    """Serve one replica until the front-end drops a ``stop`` file.

    Per loop iteration: consume inbox snapshots (read + unlink, then
    submit — a request deleted but not yet submitted when a kill lands is
    still safe: the FRONT-END journal is the source of truth and replays
    it), run one scheduler tick when work is pending (the tick beats the
    supervisor heartbeat), and append ``{"uid", "tok"}`` /
    ``{"uid", "done", "state"}`` lines to the event journal."""
    inbox = os.path.join(spool_dir, INBOX_DIR)
    os.makedirs(inbox, exist_ok=True)
    stop_path = os.path.join(spool_dir, STOP_FILE)
    seen_finished = 0
    attempt = int(os.environ.get(ENV_INCARNATION, "0"))
    # black box: tick/request spans land in the scheduler's tracer ring
    # and flush to the crash-durable flight file every few ticks — the
    # front-end folds the last flushed ring into the postmortem when
    # this process is SIGKILLed (a killed process cannot dump)
    if getattr(scheduler, "tracer", None) is None:
        name = os.path.basename(os.path.normpath(spool_dir))
        scheduler.attach_tracer(Tracer(tid=f"{name}#{attempt}"))
    recorder = FlightRecorder(scheduler.tracer,
                              flight_path(spool_dir, attempt),
                              flush_every=flight_flush_every)
    with open(events_path(spool_dir, attempt), "a") as ev:

        def flush_finished() -> None:
            nonlocal seen_finished
            fin = scheduler.finished_requests
            for req in fin[seen_finished:]:
                ev.write(json.dumps({
                    "uid": req.uid, "done": req.finish_reason,
                    "state": req.state.value,
                    "n": len(req.generated)}) + "\n")
            seen_finished = len(fin)
            ev.flush()

        while True:
            taken = 0
            for name in sorted(os.listdir(inbox)):
                path = os.path.join(inbox, name)
                try:
                    with open(path) as f:
                        snap = RequestSnapshot.from_json(f.read())
                    os.remove(path)
                except (OSError, ValueError):
                    continue      # torn write: the front-end will rewrite
                taken += 1
                try:
                    scheduler.resubmit(snap)
                except (ValueError, RuntimeError) as e:
                    # ValueError (bad snapshot / live uid) AND RuntimeError
                    # (QueueFullError burst, draining scheduler): a
                    # rejected request must become a journal event the
                    # front-end can see, never a worker crash loop
                    logger.warning(f"replica worker: rejected snapshot "
                                   f"{snap.uid}: {e}")
                    ev.write(json.dumps({"uid": snap.uid,
                                         "done": "rejected",
                                         "state": "failed", "n": 0}) + "\n")
            if taken:
                # a request that kills its host in the first tick it is
                # packed into still leaves its submit instant behind
                recorder.flush()
            if os.path.exists(stop_path):
                scheduler.shutdown(drain_deadline_s)
                flush_finished()
                os.fsync(ev.fileno())
                recorder.flush()
                return 0
            if scheduler.num_pending:
                for req, tok in scheduler.step():
                    ev.write(json.dumps({"uid": req.uid,
                                         "tok": int(tok)}) + "\n")
                recorder.tick()
            else:
                hb.tick_active()        # idle replicas are not hung
                time.sleep(poll_s)
            flush_finished()


# --------------------------------------------------------------------- #
# Front-end side
# --------------------------------------------------------------------- #
class FleetFrontEnd:
    """Supervised multi-process fleet front door (see module doc).

    ``worker_argv_fn(name, spool_dir) -> List[str]`` builds the worker
    subprocess command — it must end up calling
    :func:`run_replica_worker` over a scheduler rebuilt from checkpointed
    engine state (so respawn never depends on anything the dead process
    knew)."""

    def __init__(self, worker_argv_fn: Callable[[str, str], List[str]],
                 n_replicas: int, run_dir: str, *,
                 heartbeat_interval_s: float = 1.0,
                 hang_timeout_s: Optional[float] = None,
                 startup_timeout_s: float = 120.0,
                 max_restarts: int = 3,
                 restart_window_s: float = 300.0,
                 backoff: Optional[BackoffPolicy] = None,
                 env: Optional[Dict[str, str]] = None,
                 keep_finished: Optional[int] = None,
                 max_replays: int = 5,
                 blame: Optional[CrashBlame] = None):
        if n_replicas < 1:
            raise ValueError("FleetFrontEnd needs at least one replica")
        self.run_dir = run_dir
        os.makedirs(run_dir, exist_ok=True)
        #: flight-recorder postmortems land here on worker death /
        #: poison conviction (the spans come from the dead worker's last
        #: flushed ``flight.<attempt>.json`` ring)
        self.postmortem_dir = os.path.join(run_dir, "postmortem")
        self._postmortem_seq = itertools.count()
        self._uid_counter = itertools.count(1)
        self._rr = itertools.count()
        self.requests: Dict[int, FleetRequest] = {}
        #: O(1) load/pending reads — submit/poll must not scan the
        #: lifetime journal (same fix ServingFleet carries)
        self._outstanding_by: Dict[str, int] = {}
        #: uid -> worker currently charged with it, the AUTHORITATIVE
        #: source for the outstanding counters: ``fr.replica`` is a
        #: display trail and goes stale for queued suspects / parked
        #: requests (double-decrement hazard)
        self._home: Dict[int, str] = {}
        self._n_live = 0
        #: None keeps every FleetRequest; an int bounds journal memory on
        #: long-running front-ends by pruning the oldest finished entries
        self.keep_finished = keep_finished
        self._finished_order: List[int] = []
        self.replays = 0
        if max_replays < 1:
            raise ValueError("max_replays must be >= 1")
        #: per-request crash/reject replay cap -> reason="replay_budget"
        self.max_replays = max_replays
        #: poison-request blame/quarantine (see fleet.defense)
        self.blame = blame if blame is not None else CrashBlame()
        self.quarantined = 0
        self.replay_budget_failed = 0
        #: replica -> uid probed in isolation there (no other routing)
        self._isolating: Dict[str, int] = {}
        #: suspect uids awaiting an isolation probe
        self._suspect_queue: List[int] = []
        self.restarts_seen: Dict[str, int] = {}
        #: uids with no routable replica right now (e.g. every replica is
        #: isolating a suspect) — retried every poll, never dropped
        self._parked: List[int] = []
        #: byte offsets into event journals, keyed (replica, incarnation)
        self._offsets: Dict[tuple, int] = {}
        self.spools: Dict[str, str] = {}
        self.supervisors: Dict[str, JobSupervisor] = {}
        #: workers mid graceful retirement: the stop file is down, the
        #: drain is running — no new dispatches land there
        self._retiring: set = set()
        #: elastic lifecycle accounting (mirrors the in-process fleet's
        #: fleet/scale_* telemetry)
        self.scale_ups = 0
        self.scale_downs = 0
        self.drain_escalations = 0
        # everything _make_worker needs at add_worker time
        self._worker_argv_fn = worker_argv_fn
        self._env = dict(env or {})
        self._sup_kwargs = dict(
            heartbeat_interval_s=heartbeat_interval_s,
            hang_timeout_s=hang_timeout_s,
            startup_timeout_s=startup_timeout_s,
            max_restarts=max_restarts,
            restart_window_s=restart_window_s,
            backoff=backoff or BackoffPolicy(base_s=0.2, jitter=0.1),
            blacklist_after=max_restarts + 1,  # one host: never shrink
            min_hosts=1)
        self._worker_counter = itertools.count(n_replicas)
        for i in range(n_replicas):
            self._make_worker(f"replica{i}")
        for sup in self.supervisors.values():
            sup.start()

    def _make_worker(self, name: str) -> JobSupervisor:
        """Wire one replica worker (spool dir, inbox, supervisor) without
        starting it — the constructor batch-starts; ``add_worker`` starts
        its own."""
        from deepspeed_tpu.utils.platform import refuse_chip_children

        refuse_chip_children(len(self.spools) + 1,
                             {**os.environ, **self._env},
                             "FleetFrontEnd worker mode")
        spool = os.path.join(self.run_dir, name)
        os.makedirs(os.path.join(spool, INBOX_DIR), exist_ok=True)
        self.spools[name] = spool
        argv = self._worker_argv_fn(name, spool)

        def spec_fn(hosts, attempt, _argv=argv, _name=name,
                    _env=dict(self._env)):
            env_ = dict(_env)
            env_[ENV_INCARNATION] = str(attempt)
            return [WorkerSpec(host=_name, cmd=list(_argv), env=env_)]

        sup = JobSupervisor(spec_fn, [name],
                            run_dir=os.path.join(spool, "supervisor"),
                            **self._sup_kwargs)
        self.supervisors[name] = sup
        self.restarts_seen[name] = 0
        return sup

    # -- elastic worker lifecycle ---------------------------------------- #
    def add_worker(self, name: Optional[str] = None,
                   warmup_timeout_s: float = 120.0) -> str:
        """Spawn one more supervised replica worker and wait (bounded)
        for its first heartbeat, so the caller knows real capacity
        arrived before routing to it.  The ``scale_spawn_slow`` chaos
        point fires here — a delayed first beat must slow THIS call
        down, not trick the caller into spawning twice."""
        if name is None:
            name = f"replica{next(self._worker_counter)}"
        if name in self.spools:
            raise ValueError(f"add_worker: worker {name!r} already exists")
        from deepspeed_tpu.resilience import chaos
        chaos.fire("scale_spawn_slow", key=name)
        sup = self._make_worker(name)
        sup.start()
        deadline = time.monotonic() + warmup_timeout_s
        while time.monotonic() < deadline:
            handles = getattr(sup, "handles", None) or []
            if any(h.beat_age() is not None for h in handles):
                break
            if sup.returncode is not None:
                break        # supervisor gave up; _check_restarts raises
            time.sleep(0.02)
        self.scale_ups += 1
        logger.info(f"fleet front-end: scale-up spawned worker {name}")
        return name

    def remove_worker(self, name: str,
                      drain_deadline_s: float = 15.0) -> int:
        """Gracefully retire one worker: take it out of dispatch, drop
        the stop file (the worker drains in place and exits 0), keep
        polling so its final tokens stream out, then migrate whatever it
        could not finish to the survivors.  A worker that never finishes
        draining (``drain_stall``, SIGKILL mid-drain) is escalated at
        the deadline: the supervisor tears it down and the journal
        replays its leftovers — zero requests lost either way.  Returns
        the number of requests migrated/replayed off the victim."""
        if name not in self.spools:
            raise ValueError(f"remove_worker: unknown worker {name!r}")
        if len(self.spools) - len(self._retiring) <= 1:
            raise ValueError("remove_worker: cannot retire the last "
                             "routable worker")
        sup = self.supervisors[name]
        self._retiring.add(name)
        with open(os.path.join(self.spools[name], STOP_FILE), "w") as f:
            f.write("stop")
        deadline = time.monotonic() + drain_deadline_s
        while time.monotonic() < deadline and sup.returncode is None:
            # the poll ingests drain-finish events AND lets
            # _check_restarts journal-replay a SIGKILLed victim
            self.poll()
            if sup.returncode is None:
                time.sleep(0.02)
        escalated = sup.returncode is None
        if escalated:
            self.drain_escalations += 1
            logger.warning(
                f"fleet front-end: worker {name} drain deadline "
                f"({drain_deadline_s}s) expired — escalating to "
                "supervisor teardown + journal replay")
        sup.stop()
        # every incarnation's journal is final now: recover all flushed
        # tokens/finishes before building migration snapshots
        for old in range(self.restarts_seen[name], sup.attempt + 1):
            self._drain_events(name, attempt=old, final=True)
        leftovers = [fr for fr in self.requests.values()
                     if not fr.done and self._home.get(fr.uid) == name]
        for fr in leftovers:
            if escalated:
                fr.replays += 1
                self.replays += 1
            else:
                fr.handoffs += 1
            self._dispatch(fr)
        probe_uid = self._isolating.pop(name, None)
        if probe_uid is not None and probe_uid not in self._suspect_queue:
            self._suspect_queue.insert(0, probe_uid)
        del self.supervisors[name]
        del self.spools[name]
        self.restarts_seen.pop(name, None)
        self._outstanding_by.pop(name, None)
        self._retiring.discard(name)
        self.scale_downs += 1
        logger.info(f"fleet front-end: worker {name} retired "
                    f"({len(leftovers)} migrated, escalated={escalated})")
        return len(leftovers)

    # -- submission ----------------------------------------------------- #
    def _outstanding(self, name: str) -> int:
        return self._outstanding_by.get(name, 0)

    def _move(self, fr: FleetRequest, target: Optional[str]) -> None:
        """Re-home ``fr``'s outstanding count (``target=None`` = detached
        or done).  Keyed by the ``_home`` map, not ``fr.replica``, so a
        request already detached (suspect queue, parked) costs nothing
        a second time."""
        cur = self._home.pop(fr.uid, None)
        if cur is not None:
            self._outstanding_by[cur] = max(
                self._outstanding_by.get(cur, 0) - 1, 0)
        if target is not None:
            self._outstanding_by[target] = \
                self._outstanding_by.get(target, 0) + 1
            self._home[fr.uid] = target

    def _write_snapshot(self, name: str, snap: RequestSnapshot) -> None:
        inbox = os.path.join(self.spools[name], INBOX_DIR)
        tmp = os.path.join(inbox, f".{snap.uid}.tmp")
        with open(tmp, "w") as f:
            f.write(snap.to_json())
        os.replace(tmp, os.path.join(inbox, f"{snap.uid}.json"))

    def _dispatch(self, fr: FleetRequest) -> None:
        """Route ``fr`` to the least-outstanding replica that is NOT
        isolating a poison suspect and NOT retiring; with none routable
        (every replica probing), park it — retried each poll, never
        dropped."""
        names = [n for n in self.spools
                 if n not in self._isolating and n not in self._retiring]
        if not names:
            # detach the outstanding charge BEFORE parking: a stale
            # count on a reserved worker would gate _pump_isolation's
            # drained check forever (1-worker deadlock)
            self._move(fr, None)
            if fr.uid not in self._parked:
                self._parked.append(fr.uid)
            return
        rr = next(self._rr)
        target = min(names, key=lambda n: (
            self._outstanding(n), (names.index(n) - rr) % len(names)))
        self._move(fr, target)
        fr.replicas.append(target)
        self._write_snapshot(target, fr.snapshot())

    def submit(self, prompt, sampling: Optional[SamplingParams] = None,
               tenant: str = "default", *,
               priority_class: Optional[str] = None,
               priority: Optional[int] = None,
               deadline_s: Optional[float] = None,
               on_token=None,
               trace_id: Optional[str] = None) -> FleetRequest:
        """Journal + dispatch one request.  ``priority`` /
        ``deadline_s`` ride the spool protocol: the FleetRequest
        snapshot serializes both into the inbox record, and the worker's
        ``resubmit`` rebuilds a deadline-scheduled, priority-ordered
        Request from them — a deadline can expire ON the subprocess
        worker and journal back as a typed ``deadline`` failure.
        ``priority_class`` maps through the router's named classes
        (interactive/standard/batch) when no explicit ``priority`` is
        given."""
        if priority is None:
            if priority_class is not None:
                cls = DEFAULT_PRIORITY_CLASSES.get(priority_class)
                if cls is None:
                    raise ValueError(
                        f"submit: unknown priority class "
                        f"{priority_class!r} "
                        f"(have {sorted(DEFAULT_PRIORITY_CLASSES)})")
                priority = cls.priority
                if deadline_s is None:
                    deadline_s = cls.deadline_s
            else:
                priority = 0
        uid = next(self._uid_counter)
        fr = FleetRequest(uid=uid, prompt=[int(t) for t in prompt],
                          sampling=sampling or SamplingParams(),
                          tenant=tenant, priority=priority,
                          deadline_s=deadline_s, on_token=on_token,
                          trace_id=trace_id or mint_trace_id())
        self.requests[uid] = fr
        self._n_live += 1
        self._dispatch(fr)
        return fr

    # -- terminal bookkeeping ------------------------------------------- #
    def _prune_finished(self) -> None:
        if self.keep_finished is not None:
            while len(self._finished_order) > self.keep_finished:
                self.requests.pop(self._finished_order.pop(0), None)

    def _terminalize(self, fr: FleetRequest, reason: str,
                     error: Optional[str] = None) -> None:
        """Fail a request at the FRONT-END level (no worker owns it)."""
        if fr.done:
            return
        fr.state = "failed"
        fr.finish_reason = reason
        fr.error = error
        fr.finish_time = time.monotonic()
        self._move(fr, None)
        self._n_live -= 1
        self._finished_order.append(fr.uid)
        self._prune_finished()

    def _quarantine(self, fr: FleetRequest) -> None:
        msg = self.blame.verdict(fr.uid, host_kind="worker")
        self._terminalize(fr, "quarantined", error=msg)
        self._write_postmortem(
            reason="quarantine", replica=fr.replica or "",
            blamed_uids=[fr.uid], convicted=fr.uid,
            extra={"verdict": msg, "trace_id": fr.trace_id,
                   "death_count": self.blame.death_count(fr.uid)})
        self.blame.forget(fr.uid)
        if fr.uid in self._suspect_queue:
            self._suspect_queue.remove(fr.uid)
        self.quarantined += 1
        logger.error(f"fleet front-end: {msg}")

    # -- event ingestion ------------------------------------------------ #
    def _drain_events(self, name: str, attempt: Optional[int] = None,
                      final: bool = False) -> None:
        """Consume new journal lines from one incarnation's event file.
        Live files are read only up to the last complete line (a write
        may be mid-flush); ``final=True`` (the incarnation is dead) also
        consumes the tail — a torn tail line is skipped for good, and
        replay deterministically regenerates whatever it carried."""
        if attempt is None:
            attempt = self.restarts_seen[name]
        path = events_path(self.spools[name], attempt)
        key = (name, attempt)
        try:
            with open(path, "rb") as f:
                f.seek(self._offsets.get(key, 0))
                chunk = f.read()
        except OSError:
            return
        if not final:
            end = chunk.rfind(b"\n")
            if end < 0:
                return
            chunk = chunk[:end + 1]
        self._offsets[key] = self._offsets.get(key, 0) + len(chunk)
        for line in chunk.splitlines():
            try:
                rec = json.loads(line)
            except ValueError:
                continue             # torn tail of a dead incarnation
            fr = self.requests.get(rec.get("uid"))
            if fr is None or fr.done:
                continue
            if fr.replica != name:
                # a stale copy (e.g. an unconsumed inbox file executed by
                # a respawned worker after the request was replayed
                # elsewhere) — its stream is not the one we're tracking
                continue
            if "tok" in rec:
                fr.tokens.append(int(rec["tok"]))
                if fr.first_token_time is None:
                    fr.first_token_time = time.monotonic()
                if fr.on_token is not None:
                    fr.on_token(fr, int(rec["tok"]))
            elif "done" in rec:
                if rec["done"] in ("rejected", "shutdown") \
                        and fr.replays < self.max_replays:
                    # admission rejection (queue burst, draining worker)
                    # or a retiring worker's drain-deadline leftover:
                    # bounce to another replica instead of failing — a
                    # bounded number of times, so a truly unservable
                    # request still terminates.  A rejected ISOLATION
                    # PROBE releases its reservation and goes back to
                    # the suspect queue — never into mixed traffic
                    for iso_name, puid in list(self._isolating.items()):
                        if puid == fr.uid:
                            del self._isolating[iso_name]
                    if self.blame.is_suspect(fr.uid):
                        if fr.uid not in self._suspect_queue:
                            self._suspect_queue.append(fr.uid)
                        self._move(fr, None)
                        continue
                    if rec["done"] == "shutdown":
                        # a planned drain migration, not a crash replay
                        fr.handoffs += 1
                    else:
                        fr.replays += 1
                        self.replays += 1
                    self._dispatch(fr)
                    continue
                fr.state = ("finished" if rec.get("state") == "finished"
                            else "failed")
                fr.finish_reason = rec["done"]
                fr.finish_time = time.monotonic()
                self._move(fr, None)
                self._n_live -= 1
                self._finished_order.append(fr.uid)
                self._prune_finished()
                # terminal: the blame score table tracks LIVE uids only
                self.blame.forget(fr.uid)
                # probe resolution: the suspect finished in isolation —
                # a clean finish absolves (bad luck, not causation)
                for iso_name, puid in list(self._isolating.items()):
                    if puid == fr.uid:
                        del self._isolating[iso_name]
                        if fr.state == "finished":
                            logger.warning(
                                f"fleet front-end: suspect {puid} "
                                f"finished cleanly in isolation on "
                                f"{iso_name} — absolved")

    # -- supervision + blame + replay ----------------------------------- #
    def _check_restarts(self) -> None:
        for name, sup in self.supervisors.items():
            if sup.returncode is not None and sup.returncode != 0:
                raise RuntimeError(
                    f"fleet front-end: replica {name} is unrecoverable "
                    f"({sup.error})")
            if sup.attempt > self.restarts_seen[name]:
                # the dead incarnations' journals are final: recover every
                # flushed token BEFORE building replay snapshots
                for old in range(self.restarts_seen[name], sup.attempt):
                    self._drain_events(name, attempt=old, final=True)
                dead_attempt = sup.attempt - 1
                self.restarts_seen[name] = sup.attempt
                # unconsumed inbox files would make the respawned worker
                # re-run requests we are about to replay elsewhere
                inbox = os.path.join(self.spools[name], INBOX_DIR)
                for stale in os.listdir(inbox):
                    try:
                        os.remove(os.path.join(inbox, stale))
                    except OSError:
                        pass
                # whatever probe ran here resolved — by killing its
                # host, the strongest conviction evidence
                probe_uid = self._isolating.pop(name, None)
                # parked/queued requests are not ON this worker: their
                # own retry paths continue them; replaying here too would
                # run the same uid twice
                waiting = set(self._parked) | set(self._suspect_queue)
                lost = [fr for fr in self.requests.values()
                        if not fr.done and fr.replica == name
                        and fr.uid not in waiting]
                # journal the incarnation death's exact in-flight set
                blame_set = {fr.uid for fr in lost}
                if blame_set:
                    self.blame.record_death(blame_set, replica=name,
                                            reason="crash")
                probed = (probe_uid is not None
                          and blame_set == {probe_uid})
                convicted, suspect_uids, _ = \
                    self.blame.classify_lost(blame_set, probed=probed) \
                    if blame_set else (None, [], [])
                if suspect_uids or self._suspect_queue:
                    # RESERVE the respawned worker for isolation BEFORE
                    # redispatching innocents — under sustained traffic
                    # no worker ever reads idle, and an unreserved probe
                    # would starve in the queue forever
                    self._isolating.setdefault(name, None)
                replayed = 0
                for fr in lost:
                    if convicted is not None and fr.uid == convicted:
                        self._quarantine(fr)
                    elif fr.uid in suspect_uids:
                        # suspects never re-enter mixed traffic: they
                        # wait for an isolation probe on an idle worker
                        if fr.uid not in self._suspect_queue:
                            self._suspect_queue.append(fr.uid)
                        self._move(fr, None)
                    elif fr.replays >= self.max_replays:
                        self._terminalize(
                            fr, "replay_budget",
                            error=(f"request {fr.uid} exceeded "
                                   f"max_replays={self.max_replays} "
                                   f"crash replays"))
                        self.blame.forget(fr.uid)
                        self.replay_budget_failed += 1
                    else:
                        fr.replays += 1
                        self.replays += 1
                        self._dispatch(fr)
                        replayed += 1
                # flight recorder: the dead incarnation's last flushed
                # span ring + this death's verdicts, one postmortem file
                self._write_postmortem(
                    reason="crash", replica=name,
                    blamed_uids=blame_set, convicted=convicted,
                    suspects=suspect_uids,
                    spans=FlightRecorder.read_flight(
                        flight_path(self.spools[name], dead_attempt)),
                    extra={"attempt": dead_attempt})
                logger.warning(
                    f"fleet front-end: replica {name} restarted "
                    f"(attempt {sup.attempt}) — {replayed} replayed, "
                    f"suspects={self._suspect_queue}, "
                    f"quarantined="
                    f"{convicted if convicted is not None else 'none'}")
        self._pump_isolation()

    def _write_postmortem(self, *, reason: str, replica: str,
                          blamed_uids, convicted=None, suspects=(),
                          spans=(), extra=None) -> str:
        path = os.path.join(
            self.postmortem_dir,
            f"{next(self._postmortem_seq):04d}.{replica or 'frontend'}"
            f".{reason}.json")
        return write_postmortem(
            path, reason=reason, replica=replica,
            blamed_uids=blamed_uids, convicted=convicted,
            suspects=suspects, spans=spans, extra=extra)

    def _pump_isolation(self) -> None:
        """Dispatch queued suspects, each ALONE onto a worker with
        nothing outstanding (the respawned one qualifies: its in-flight
        set was just replayed away).  ``_dispatch`` routes innocent
        traffic around isolating workers, so the next death there has a
        singleton in-flight set — and convicts."""
        while self._suspect_queue:
            # reserved workers (value None: set aside at death time,
            # before innocents could be redispatched there) first, then
            # any fully idle unreserved worker
            cands = [n for n, v in self._isolating.items()
                     if v is None and self._outstanding(n) == 0]
            cands += [n for n in self.spools
                      if n not in self._isolating
                      and self._outstanding(n) == 0]
            if not cands:
                return                      # retry next poll
            uid = self._suspect_queue[0]
            fr = self.requests.get(uid)
            if fr is None or fr.done:
                self._suspect_queue.pop(0)
                continue
            self._suspect_queue.pop(0)
            name = cands[0]
            self._isolating[name] = uid
            fr.replays += 1
            self.replays += 1
            self._move(fr, name)
            fr.replicas.append(name)
            self._write_snapshot(name, fr.snapshot())
            logger.warning(f"fleet front-end: probing suspect request "
                           f"{uid} in isolation on {name}")
        # queue drained: release any leftover reservations so the
        # workers rejoin normal dispatch
        for n, v in list(self._isolating.items()):
            if v is None:
                del self._isolating[n]

    # -- driving -------------------------------------------------------- #
    @property
    def num_pending(self) -> int:
        return self._n_live

    def step(self) -> None:
        """Fleet-shaped alias for the gateway pump / replay harness: one
        front-end poll (the actual scheduler ticks happen inside the
        worker subprocesses)."""
        self.poll()

    def poll(self) -> None:
        for name in self.spools:
            self._drain_events(name)
        self._check_restarts()
        if self._parked:
            parked, self._parked = self._parked, []
            for uid in parked:
                fr = self.requests.get(uid)
                if fr is not None and not fr.done:
                    self._dispatch(fr)      # may re-park

    def run_until_idle(self, timeout_s: float = 120.0,
                       poll_s: float = 0.02) -> List[FleetRequest]:
        deadline = time.monotonic() + timeout_s
        while self.num_pending and time.monotonic() < deadline:
            self.poll()
            if self.num_pending:
                time.sleep(poll_s)
        self.poll()
        return list(self.requests.values())

    def stop(self, timeout_s: float = 60.0) -> None:
        """Drop stop files (workers drain and exit 0), join the
        supervisors, escalate through ``JobSupervisor.stop`` for
        stragglers."""
        for spool in self.spools.values():
            with open(os.path.join(spool, STOP_FILE), "w") as f:
                f.write("stop")
        deadline = time.monotonic() + timeout_s
        for name, sup in self.supervisors.items():
            sup.wait(timeout=max(deadline - time.monotonic(), 0.1))
        for sup in self.supervisors.values():
            sup.stop()
