"""The one general traffic generator.  A traffic mix is a data file of
parameters (``benchmark/traffic/<mix>.json``); everything here is drawn from
``--seed`` with numpy's ``default_rng`` over explicit seed sequences, so the
same seed gives byte-identical schedules, prompts and batches, and streams
(arrivals, lengths, prompts, batches) do not disturb one another.

Mix kinds
---------
``open_loop``    arrivals on a schedule, whether or not earlier requests
                 finished: ``arrivals`` {rate_per_s}, a Poisson process
                 conditioned on its count: round(rate x length) arrivals at
                 sorted uniform times, in the pre-roll and in the window
                 separately, so every run offers the same number of
                 requests.  Lengths are drawn without replacement
                 from the ``stratify_block`` (B) quantile midpoints of their
                 distribution, block after block, each block in a seeded
                 order: every B consecutive requests carry the same multiset
                 of lengths, so a run is a fixed amount of work drawn from
                 the seed.
``closed_loop``  ``clients`` callers, each sending its next request when its
                 last one finished.  Every client keeps its own lengths:
                 client c of n sends prompts at quantile (c + 0.5) / n of
                 the distribution and outputs at another fixed quantile
                 (clients differ from each other more than one client's
                 requests do).  The seed gives the token ids and, with
                 ``start_stagger_s``, when in [0, start_stagger_s) each
                 client sends its first request: the loop settles into a
                 cycle, and which cycle depends on those offsets.
``train_steps``  fixed-shape steps: ``global_batch`` x ``seq_len`` tokens, a
                 fresh batch every step.

Serving mixes give ``prompt_tokens`` / ``output_tokens`` as a distribution
({dist: lognormal, median, sigma, min, max} | {dist: uniform, min, max}).
The length arithmetic follows ``gateway/loadgen.py::synth_trace``; the
replayer there is not used: this harness times from the due time itself.
"""

from __future__ import annotations

import dataclasses
import hashlib
import statistics
from typing import Dict, Iterator, List, Optional

import numpy as np

# stream ids: one independent generator per purpose
_ARRIVALS, _LENGTHS, _PROMPT, _BATCH, _CLIENT, _STAGGER = 0, 1, 2, 4, 5, 6


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


@dataclasses.dataclass
class PlannedRequest:
    rid: int
    due_s: Optional[float]        # offset from the window's start; None =
    prompt_len: int               # closed loop (due when the client is free)
    output_len: int


_NORMAL = statistics.NormalDist()


def draw(rng: np.random.Generator, dist: dict, n: int,
         block: int) -> np.ndarray:
    """``n`` integer lengths: the distribution's quantile function at the
    midpoints (i + 0.5) / block, in a fresh seeded order per block."""
    u = [(rng.permutation(block) + 0.5) / block
         for _ in range(-(-n // block))]
    return _quantiles(dist, np.concatenate(u)[:n] if u else np.zeros((0,)))


def _quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    """The distribution's quantile function at ``u``, as integer lengths."""
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = int(dist["min"]), int(dist["max"])
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(
            np.int64)
    if kind == "lognormal":
        z = np.asarray([_NORMAL.inv_cdf(float(x)) for x in u], np.float64)
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
        return np.clip(np.rint(x), int(dist["min"]),
                       int(dist["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def arrival_times(mix: dict, seed: int, start_s: float,
                  end_s: float) -> np.ndarray:
    """Due times in [start_s, end_s), seconds from the window's start: a
    Poisson process conditioned on its count, the pre-roll (before 0) and
    the window each with round(rate x length) arrivals."""
    rate = float(mix["arrivals"]["rate_per_s"])
    rng = _rng(seed, _ARRIVALS)
    parts = []
    for a, b in ((start_s, min(0.0, end_s)), (max(0.0, start_s), end_s)):
        if b > a:
            parts.append(np.sort(rng.uniform(
                a, b, size=int(round((b - a) * rate)))))
    return np.concatenate(parts) if parts else np.zeros((0,))


def open_loop_plan(mix: dict, seed: int, start_s: float,
                   end_s: float) -> List[PlannedRequest]:
    due = arrival_times(mix, seed, start_s, end_s)
    rng = _rng(seed, _LENGTHS)
    block = int(mix["stratify_block"])
    # the window's requests are drawn first, so that its blocks are whole
    # whatever the pre-roll holds
    order = np.concatenate([np.flatnonzero(due >= 0),
                            np.flatnonzero(due < 0)]).astype(np.int64)
    plen = np.empty(len(due), np.int64)
    olen = np.empty(len(due), np.int64)
    plen[order] = draw(rng, mix["prompt_tokens"], len(due), block)
    olen[order] = draw(rng, mix["output_tokens"], len(due), block)
    return [PlannedRequest(i, float(d), int(p), int(o))
            for i, (d, p, o) in enumerate(zip(due, plen, olen))]


class ClosedLoop:
    """A closed loop's clients: each with its own fixed lengths, and a
    seeded moment for its first request."""

    def __init__(self, mix: dict, seed: int):
        self.n = int(mix["clients"])
        self._k = 0
        q = (np.arange(self.n) + 0.5) / self.n
        # which output quantile each client has: fixed, not from --seed
        perm = _rng(0, _CLIENT, self.n).permutation(self.n)
        self._plen = _quantiles(mix["prompt_tokens"], q)
        self._olen = _quantiles(mix["output_tokens"], q[perm])
        self.first_due_s = _rng(seed, _STAGGER).uniform(
            0.0, float(mix.get("start_stagger_s", 0.0)), size=self.n)

    def next(self, client: int) -> PlannedRequest:
        self._k += 1
        return PlannedRequest(self._k - 1, None, int(self._plen[client]),
                              int(self._olen[client]))


def prompt_tokens(seed: int, req: PlannedRequest, vocab: int) -> List[int]:
    """The request's own seeded token ids."""
    return _rng(seed, _PROMPT, req.rid).integers(
        0, vocab, size=(req.prompt_len,)).tolist()


def train_batches(mix: dict, seed: int, vocab: int) -> Iterator[np.ndarray]:
    """Fresh seeded ``[global_batch, seq_len]`` int32 batches, the same
    order for a seed.  Uniform random tokens: the loss stays near
    ln(vocab), which is what the window's loss band checks."""
    shape = (int(mix["global_batch"]), int(mix["seq_len"]))
    step = 0
    while True:
        yield _rng(seed, _BATCH, step).integers(
            0, vocab, size=shape).astype(np.int32)
        step += 1


def fingerprint(mix: dict, seed: int, vocab: int = 32000) -> str:
    """sha256 over everything a run of ``mix`` would draw first — what the
    tests use to show that a seed reproduces its traffic byte for byte."""
    h = hashlib.sha256()
    if mix["kind"] == "train_steps":
        it = train_batches(mix, seed, vocab)
        for _ in range(3):
            h.update(next(it).tobytes())
        return h.hexdigest()
    if mix["kind"] == "open_loop":
        reqs = open_loop_plan(mix, seed, -float(mix.get("preroll_s", 0)), 30.0)
    else:
        loop = ClosedLoop(mix, seed)
        h.update(loop.first_due_s.tobytes())
        reqs = [loop.next(i % loop.n) for i in range(64)]
    for r in reqs[:64]:
        h.update(repr(dataclasses.astuple(r)).encode())
        h.update(np.asarray(prompt_tokens(seed, r, vocab), np.int32).tobytes())
    return h.hexdigest()


def summary(reqs: List[PlannedRequest]) -> Dict[str, float]:
    """What was drawn, for the line before the result."""
    if not reqs:
        return {"requests": 0}
    p = np.asarray([r.prompt_len for r in reqs])
    o = np.asarray([r.output_len for r in reqs])
    return {"requests": len(reqs), "prompt_mean": float(p.mean()),
            "prompt_p50": float(np.median(p)), "prompt_max": int(p.max()),
            "output_mean": float(o.mean()), "output_p50": float(np.median(o))}
