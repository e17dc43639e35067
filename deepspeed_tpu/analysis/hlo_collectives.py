"""List the collectives of a compiled program from its HLO text.

``collectives(compiled.as_text())`` gives one :class:`Collective` per
collective instruction: its kind, the dtype and shape of each array it
produces, and the ``op_name`` scope XLA recorded for it.  A placement test
reads these instead of timing anything: which collectives the SPMD
partitioner put in, on what (a parameter or an activation), and under which
``jax.named_scope``.  Nothing here runs a program.

The TPU compiler writes a reduce-scatter as a fused computation named
``all-reduce-scatter*`` that holds an ``all-reduce`` and a ``dynamic-slice``;
the ``all-reduce`` inside one is listed as kind ``reduce-scatter`` with the
fusion's (scattered) result.  It also clones one asynchronous collective into
several fused computations that share its ``channel_id``: those are listed
once.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Tuple

KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")

_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(
    r"=\s*(?P<type>\(.*?\)|\S+)\s+(?P<op>" + "|".join(KINDS)
    + r")(?P<start>-start)?\(")
_COMPUTATION = re.compile(r"^%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->\s*(?P<type>.+?)\s*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CHANNEL = re.compile(r"\bchannel_id=(\d+)")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}


@dataclass(frozen=True)
class Collective:
    kind: str
    # (dtype, shape) of each array the collective produces, per device
    results: Tuple[Tuple[str, Tuple[int, ...]], ...]
    scope: str

    @property
    def nbytes(self) -> int:
        total = 0
        for dtype, shape in self.results:
            n = _DTYPE_BYTES[dtype]
            for d in shape:
                n *= d
            total += n
        return total

    @property
    def max_rank(self) -> int:
        return max((len(shape) for _, shape in self.results), default=0)


def _arrays(type_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dtype, tuple(int(d) for d in dims.split(",") if d))
            for dtype, dims in _ARRAY.findall(type_text)
            if dtype in _DTYPE_BYTES]


def collectives(hlo_text: str) -> List[Collective]:
    """Every collective instruction of ``hlo_text`` (a ``-done`` is not a
    second collective; an async ``-start`` lists what it will produce)."""
    found: List[Collective] = []
    channels = set()
    scatter_result = None    # inside an all-reduce-scatter fusion body
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line.strip()) if line[:1] not in " \t" \
            else None
        if head is not None:
            scatter_result = _arrays(head["type"]) \
                if head["name"].startswith("all-reduce-scatter") else None
            continue
        m = _INSTR.search(line)
        if m is None:
            continue
        channel = _CHANNEL.search(line)
        if channel is not None:
            if channel[1] in channels:
                continue
            channels.add(channel[1])
        kind, arrays = m["op"], _arrays(m["type"])
        if m["start"] and kind in ("all-gather", "collective-permute"):
            # (operands..., results...[, sync scalars]) -- keep the results
            arrays = [a for a in arrays if a[1] or a[0] not in ("u32", "s32")]
            arrays = arrays[len(arrays) // 2:]
        if kind == "all-reduce" and scatter_result is not None:
            kind, arrays = "reduce-scatter", scatter_result
        scope = _OP_NAME.search(line)
        found.append(Collective(kind, tuple(arrays),
                                scope[1] if scope else ""))
    return found


def summary(found: List[Collective]) -> Dict[str, Dict[str, int]]:
    """Instruction count and bytes produced, by kind."""
    out = {k: {"count": 0, "bytes": 0} for k in KINDS}
    for c in found:
        out[c.kind]["count"] += 1
        out[c.kind]["bytes"] += c.nbytes
    return out
