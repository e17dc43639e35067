"""Several requests of different lengths served TOGETHER through the
scheduler (SplitFuse chunks of one beside decodes of another, joins and
leaves, the decode step dispatched ahead), each one's logits compared with
its own plain reference forward.

The benchmark's ``_check_logits`` (``benchmark/runners/serve_ragged.py``)
feeds ONE sequence: state slots mixed up between sequences, a chunk that
picks up its neighbour's convolution tail, a slot reused without a reset
show only when several sequences share batches.  The scheduler hands out
tokens, not logits, so the engine's ``launch`` and ``decode_step`` are
wrapped to keep the device logits of every step program it runs (fetched
after the run); the requests sample greedily, and the reference is run
teacher-forced over each request's prompt plus the tokens the engine itself
generated.

Every ragged forward is a ``launch`` since PR 40 (``put`` is ``prepare`` +
``launch``, and a scheduler that runs ahead calls the two itself, with no
``put`` around them); it returns the logits beside the tokens, unfetched, so
nothing is asked of the program that the scheduler does not ask.
``benchmark/tools/interleaved_check.py`` is this file as of PR 31, written
against a ``put`` without ``greedy``; it cannot serve a scheduler that
passes it (PERF.md section 7).

Used by ``chip_smoke.py`` (two sequences, depth cut) and the
``test_interleaved_logits_match_each_reference`` tests.
"""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np


def serve_and_compare(engine, reference, ref_params, hf: Dict[str, Any],
                      prompts: Sequence[Sequence[int]],
                      new_tokens: Sequence[int],
                      stagger_ticks: int = 2) -> Dict[str, Any]:
    """Returns ``{"gaps": [per request: largest |logit difference| over
    the largest |reference logit|], "rows": [logit rows compared],
    "ticks": ..., "generated": [tokens a request]}``.  Request ``i`` is
    submitted ``i * stagger_ticks`` ticks after the first, so later
    prompts' chunks share batches with earlier requests' decodes."""
    import jax

    from deepspeed_tpu.serving import ContinuousBatchScheduler, SamplingParams

    sm = engine.state_manager
    seen: List[Any] = []            # (uid, position of the fed token, row)

    real_launch, real_step = engine.launch, engine.decode_step

    def launch(prepared, late_tokens=None):
        # every ragged forward goes through here: ``put``'s own, and the
        # batches a scheduler that runs ahead prepares and launches itself
        logits, nxt, n = real_launch(prepared, late_tokens)
        for slot, (uid, last) in enumerate(zip(prepared.scheduled,
                                               prepared.drained)):
            if last:
                seen.append((uid, sm.get_sequence(uid).seen_tokens - 1,
                             (logits, slot)))
        return logits, nxt, n

    def decode_step(uids, tokens, greedy=False, rows=None):
        out = real_step(uids, tokens, greedy=greedy, rows=rows)
        logits = out[0] if greedy else out
        for i, uid in enumerate(uids):
            seen.append((uid, sm.get_sequence(uid).seen_tokens - 1,
                         (logits, i)))
        return out

    engine.launch, engine.decode_step = launch, decode_step
    try:
        sched = ContinuousBatchScheduler(engine)
        reqs, ticks = [], 0
        pending = list(zip(prompts, new_tokens))
        while pending or sched.num_pending:
            if pending and ticks >= len(reqs) * stagger_ticks:
                p, n = pending.pop(0)
                reqs.append(sched.submit(list(p), SamplingParams(
                    greedy=True, max_new_tokens=int(n))))
            if sched.num_pending:
                sched.step()
            ticks += 1
    finally:
        engine.launch, engine.decode_step = real_launch, real_step

    rows_by_uid: Dict[int, Dict[int, np.ndarray]] = {}
    fetched: Dict[int, np.ndarray] = {}
    for uid, pos, row in seen:
        if isinstance(row, tuple):
            arr, i = row
            if id(arr) not in fetched:
                fetched[id(arr)] = np.asarray(jax.device_get(arr),
                                              np.float32)
            row = fetched[id(arr)][i]
        rows_by_uid.setdefault(uid, {})[pos] = np.asarray(row, np.float32)

    gaps, n_rows = [], []
    for req in reqs:
        ids = np.asarray(list(req.prompt) + list(req.generated), np.int64)
        # a row fed past the request's end (the step dispatched ahead of a
        # finish) has no reference position
        got = {p: r for p, r in rows_by_uid.get(req.uid, {}).items()
               if p < len(ids)}
        pos = sorted(got)
        want = reference.logits_at(ref_params, ids, hf, rows=pos)
        have = np.stack([got[p] for p in pos])
        if not np.isfinite(have).all():
            gaps.append(float("inf"))
        else:
            gaps.append(float(np.max(np.abs(have - want))
                              / np.max(np.abs(want))))
        n_rows.append(len(pos))
        # every generated token but the last was fed back and scored
        assert len(pos) >= len(req.generated), (req.uid, len(pos))
    return {"gaps": gaps, "rows": n_rows, "ticks": ticks,
            "generated": [len(r.generated) for r in reqs]}
