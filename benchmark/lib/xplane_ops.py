"""The device operations of an ``.xplane.pb`` with what the profiler keeps
about each instruction and ``jax.profiler.ProfileData`` does not hand out.

A TPU profile stores, once per distinct HLO instruction, an *event metadata*
record: the instruction's text (the event's name) and statistics such as
``tf_op`` (the instruction's ``op_name``: ``jit(run)/layers_0/mlp/dot_general``,
with a trailing colon), ``hlo_category``, ``flops``, ``bytes_accessed``,
``source``.  An event of the "XLA Ops" line only points at its record;
``ProfileData`` resolves the name and drops the record's statistics (seen on
jax 0.9.0, TPU v5 lite, PR 23).  So this module reads the file's protobuf
wire format itself: the few messages of ``xplane.proto`` it needs (XSpace,
XPlane, XLine, XEvent, XEventMetadata, XStatMetadata, XStat), nothing but
the standard library.  Times come out as ``ProfileData`` gives them
(``line.timestamp_ns + offset_ps / 1000``), so they share a clock with
``lib/tracing.TraceView``.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Tuple

OP_LINE = "XLA Ops"


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        no, wt = key >> 3, key & 7
        if wt == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield no, wt, val
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield no, wt, buf[i:i + ln]
            i += ln
        elif wt == 1:
            yield no, wt, buf[i:i + 8]
            i += 8
        elif wt == 5:
            yield no, wt, buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")


def _map_entry(buf) -> Tuple[int, object]:
    key, val = 0, b""
    for no, _wt, v in _fields(buf):
        if no == 1:
            key = v
        elif no == 2:
            val = v
    return key, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def device_ops(path: str, stat: str = "tf_op"
               ) -> List[Tuple[int, int, int, str, str]]:
    """[(device index, start ns, end ns, ``stat`` of the instruction or "",
    instruction text)] for every event of the "XLA Ops" line of every
    ``/device:TPU:<n>`` plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: List[Tuple[int, int, int, str, str]] = []
    for no, wt, plane in _fields(space):
        if no != 1 or wt != 2:
            continue
        top = list(_fields(plane))
        name = next((_text(v) for n_, _w, v in top if n_ == 2), "")
        m = re.match(r"/device:TPU:(\d+)$", name)
        if not m:
            continue
        dev = int(m.group(1))
        stat_names: Dict[int, str] = {}
        for n_, _w, v in top:
            if n_ == 5:                                   # stat_metadata
                k, body = _map_entry(v)
                stat_names[k] = next((_text(x) for f_, _w2, x in
                                      _fields(body) if f_ == 2), "")
        want = {k for k, v in stat_names.items() if v == stat}
        meta: Dict[int, Tuple[str, str]] = {}             # id -> (text, stat)
        for n_, _w, v in top:
            if n_ != 4:                                   # event_metadata
                continue
            k, body = _map_entry(v)
            text, value = "", ""
            for f_, _w2, x in _fields(body):
                if f_ == 2:
                    text = _text(x)
                elif f_ == 5:                             # XStat
                    sid, sval = 0, ""
                    for g, _w3, y in _fields(x):
                        if g == 1:
                            sid = y
                        elif g == 5:                      # str_value
                            sval = _text(y)
                        elif g == 7:                      # ref_value
                            sval = stat_names.get(y, "")
                    if sid in want:
                        value = sval
            meta[k] = (text, value)
        for n_, _w, v in top:
            if n_ != 3:                                   # lines
                continue
            line = list(_fields(v))
            if next((_text(x) for f_, _w2, x in line if f_ == 2), "") \
                    != OP_LINE:
                continue
            t0 = next((x for f_, _w2, x in line if f_ == 3), 0)
            for f_, _w2, ev in line:
                if f_ != 4:
                    continue
                mid = off = dur = 0
                for g, _w3, y in _fields(ev):
                    if g == 1:
                        mid = y
                    elif g == 2:
                        off = y
                    elif g == 3:
                        dur = y
                text, value = meta.get(mid, ("", ""))
                start = t0 + off // 1000
                out.append((dev, start, start + dur // 1000, value, text))
    return out
