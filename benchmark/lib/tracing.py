"""From the profiler's trace to intervals, and the arithmetic on intervals.

``capture`` wraps ``jax.profiler.start_trace`` / ``stop_trace``.
``TraceView.from_xplane`` reads the ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into two flat lists:

* device events — one per operation that ran on a chip (the device planes'
  "XLA Ops" line; "Async XLA Ops" holds the asynchronous collectives and
  copies from start to done and is kept apart): device index, start and
  duration in nanoseconds.  On the TPU an event's name is the whole text of
  its HLO instruction; ``label`` (what the readers' patterns match) is cut
  from it as ``<instruction> <opcode> [<custom_call_target>]``, e.g.
  ``paged_attention.16 custom-call tpu_custom_call`` (a Mosaic kernel takes
  the name of the jitted function that wraps it, or of the flax scope it
  was called in; the kernel function's own name is NOT in the trace),
  ``all-gather-start.3 all-gather-start``, ``fusion.2709 fusion``;
* host events — ``TraceAnnotation`` spans of the process's threads (the
  harness's own ``bench/...`` spans and the program's ``annotate()`` spans).

Everything after that (busy union, sums by name, exposed collective time,
idle gaps labelled by the enclosing host span) is plain arithmetic on
intervals, also usable on hand-made intervals — that is how the tests check
it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

#: line of a device plane with one event per executed operation, and the
#: line with asynchronous operations from their start to their done
_OP_LINE, _ASYNC_LINE = "XLA Ops", "Async XLA Ops"
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all", re.I)


@dataclasses.dataclass
class DeviceEvent:
    device: int
    name: str
    label: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class HostEvent:
    thread: str
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


# ------------------------------------------------------------------ #
# interval arithmetic
# ------------------------------------------------------------------ #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[List[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` (disjoint, sorted) that no interval of ``b``
    (disjoint, sorted) covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# ------------------------------------------------------------------ #
# capture
# ------------------------------------------------------------------ #
@contextlib.contextmanager
def capture(trace_dir: str):
    """Profile the enclosed region into ``trace_dir`` (emptied first).
    Python-level tracing is off: the spans that matter are the explicit
    ``TraceAnnotation`` ones, and the interpreter hook would slow the host
    loop that is being measured.  Yields a dict that receives ``xplane``
    (the file) and ``mono_sync_ns``."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    info: Dict[str, object] = {}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # a span whose start is known on both clocks: lets spans recorded on
    # time.monotonic_ns (the program's Tracer) be moved onto the profiler's
    with jax.profiler.TraceAnnotation("bench/clock_sync"):
        info["mono_sync_ns"] = time.monotonic_ns()
    try:
        yield info
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        info["xplane"] = max(found, key=os.path.getmtime) if found else None


# ------------------------------------------------------------------ #
# the view
# ------------------------------------------------------------------ #
class TraceView:
    def __init__(self, device_events: List[DeviceEvent],
                 host_events: List[HostEvent],
                 async_events: Optional[List[DeviceEvent]] = None):
        self.device_events = device_events
        self.host_events = host_events
        self.async_events = async_events or []
        self.devices = sorted({e.device for e in device_events})

    # -- reading ------------------------------------------------------ #
    @classmethod
    def from_xplane(cls, path: str) -> "TraceView":
        import jax

        data = jax.profiler.ProfileData.from_file(path)
        dev: List[DeviceEvent] = []
        asyn: List[DeviceEvent] = []
        host: List[HostEvent] = []
        for plane in data.planes:
            m = re.match(r"/device:TPU:(\d+)$", plane.name)
            if m:
                idx = int(m.group(1))
                for line in plane.lines:
                    if line.name not in (_OP_LINE, _ASYNC_LINE):
                        continue
                    into = dev if line.name == _OP_LINE else asyn
                    for ev in line.events:
                        into.append(DeviceEvent(
                            idx, ev.name, label_of(ev.name),
                            int(ev.start_ns), int(ev.duration_ns)))
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.duration_ns <= 0:
                            continue
                        host.append(HostEvent(line.name, ev.name,
                                              int(ev.start_ns),
                                              int(ev.duration_ns)))
        return cls(dev, host, asyn)

    # -- device ------------------------------------------------------- #
    def window(self) -> Interval:
        """First start to last end over all device events."""
        if not self.device_events:
            return (0, 0)
        return (min(e.start for e in self.device_events),
                max(e.end for e in self.device_events))

    def busy(self, device: int) -> List[Interval]:
        return union((e.start, e.end) for e in self.device_events
                     if e.device == device)

    def busy_seconds(self, lo: Optional[int] = None,
                     hi: Optional[int] = None) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if not self.devices:
            return 0.0
        w = self.window()
        lo, hi = (w[0] if lo is None else lo), (w[1] if hi is None else hi)
        return sum(total(clip(self.busy(d), lo, hi))
                   for d in self.devices) / len(self.devices) / 1e9

    def matching(self, pattern: str) -> List[DeviceEvent]:
        rx = re.compile(pattern)
        return [e for e in self.device_events if rx.search(e.label)]

    def seconds_matching(self, pattern: str, lo: Optional[int] = None,
                         hi: Optional[int] = None) -> float:
        """Device seconds of the operations whose label matches, averaged
        over the devices (union per device, so an operation that the trace
        lists with its children is not counted twice)."""
        if not self.devices:
            return 0.0
        w = self.window()
        lo, hi = (w[0] if lo is None else lo), (w[1] if hi is None else hi)
        per_dev: Dict[int, List[Interval]] = {d: [] for d in self.devices}
        for e in self.matching(pattern):
            per_dev[e.device].append((e.start, e.end))
        return sum(total(clip(union(v), lo, hi))
                   for v in per_dev.values()) / len(self.devices) / 1e9

    def collective_seconds(self) -> Tuple[float, float]:
        """(all, exposed) seconds of collective operations, averaged over
        the devices.  A collective's time runs from its start to its done
        where it is asynchronous.  Exposed = while no other operation ran
        on that device (the ``-done`` that waits for a transfer is itself a
        collective operation, so waiting counts as exposed)."""
        if not self.devices:
            return 0.0, 0.0
        all_s = exposed_s = 0
        for d in self.devices:
            coll = union((e.start, e.end)
                         for e in self.device_events + self.async_events
                         if e.device == d and _COLLECTIVE.search(e.label))
            rest = union((e.start, e.end) for e in self.device_events
                         if e.device == d
                         and not _COLLECTIVE.search(e.label))
            all_s += total(coll)
            exposed_s += total(subtract(coll, rest))
        n = len(self.devices)
        return all_s / n / 1e9, exposed_s / n / 1e9

    def top_ops(self, n: int = 10) -> List[List[object]]:
        """The operations with most device time, summed under ``op_key``
        (instance numbers folded, the result shape kept), seconds averaged
        over the devices."""
        acc: Dict[str, int] = {}
        for e in self.device_events:
            key = op_key(e)
            acc[key] = acc.get(key, 0) + e.dur
        nd = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / nd / 1e9] for k, v in top]

    # -- host --------------------------------------------------------- #
    def host_named(self, pattern: str) -> List[HostEvent]:
        rx = re.compile(pattern)
        return sorted((e for e in self.host_events if rx.search(e.name)),
                      key=lambda e: e.start)

    def idle_gaps(self, labels: Sequence[Tuple[str, int, int]],
                  lo: Optional[int] = None, hi: Optional[int] = None,
                  n: int = 10) -> List[List[object]]:
        """Idle seconds of the first device, summed by what the host was
        doing: each gap between busy intervals is given the name of the
        shortest ``labels`` span (name, start, end) that contains its
        midpoint, else "(no span)".  The ``n`` largest sums."""
        if not self.devices:
            return []
        w = self.window()
        lo, hi = (w[0] if lo is None else lo), (w[1] if hi is None else hi)
        spans = sorted(labels, key=lambda s: s[1])
        acc: Dict[str, int] = {}
        nxt = 0
        live: List[Tuple[str, int, int]] = []   # started, may still cover
        for s, e in gaps(self.busy(self.devices[0]), lo, hi):
            mid = (s + e) // 2
            while nxt < len(spans) and spans[nxt][1] <= mid:
                live.append(spans[nxt])
                nxt += 1
            live = [sp for sp in live if sp[2] >= mid]
            key = min(live, key=lambda sp: sp[2] - sp[1])[0] if live \
                else "(no span)"
            acc[key] = acc.get(key, 0) + (e - s)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]


_INSTR = re.compile(r"^%?([^ =]+) = (.*)$", re.S)
_OPCODE = re.compile(r"(?:^|[ )}\]])([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def label_of(text: str) -> str:
    """``<instruction> <opcode> [<custom_call_target>]`` from the text of
    an HLO instruction; a name that is not HLO text is its own label."""
    m = _INSTR.match(text)
    if not m:
        return text
    name, rest = m.groups()
    op = _OPCODE.search(rest)
    tgt = _TARGET.search(rest)
    return " ".join(x for x in (name, op.group(1) if op else "",
                                tgt.group(1) if tgt else "") if x)


def op_key(e: DeviceEvent) -> str:
    """Name under which an operation is summed in the breakdown: the
    instruction name without its instance number, the custom-call target,
    and the first result shape (what tells one ``fusion`` from another)."""
    parts = e.label.split(" ")
    key = re.sub(r"[.\d]+$", "", parts[0]) or parts[0]
    if len(parts) > 2:
        key += " [" + parts[2] + "]"
    m = _INSTR.match(e.name)
    shape = _SHAPE.match(m.group(2)) if m else None
    return key + (" " + shape.group(1) if shape else "")
