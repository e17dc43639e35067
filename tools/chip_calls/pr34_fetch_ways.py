"""PR 34, one-off for the chip: the same mixed tick fetched both ways.

On the Moonlight and the Mistral serving configurations at the published widths (the engines
``benchmark/runners/serve_ragged.py`` builds, seeded weights), a full mixed tick of the closed
loops: every slot but one decodes a token, the last holds a chunk of a long prompt that fills the
token budget (Moonlight 63 + 961 tokens in ``ragged_step_T1088_tiled``, Mistral 31 + 993 in
``T1056_tiled``).  The same seeded script of ticks is served twice on one engine:

* ``logits``: ``put`` as every tick ran before PR 34: the ``[max_seqs, vocab]`` float32 logits
  cross to the host, ``sample_batch`` takes the argmax there;
* ``tokens``: ``put(greedy=True)``: the program's own argmax crosses, one int32 a row;
* ``tokens_polled`` (call 3): the same, but the dispatch does not return before the host has seen
  ``is_ready()`` of the token vector in a busy loop, so the ``device_get`` that follows finds it
  there.  Call 2's traced runs read the blocking wait of a 40-57 ms program returning 1.1-1.3 ms
  after the program's end whatever it fetches (0.04 ms for a decode step): the difference between
  ``tokens`` and ``tokens_polled`` says how much of that is the runtime's blocking wait.

It checks that the two ways hand out the same tokens, tick by tick and row by row (the tokens are
fed back, so a difference anywhere would also change what follows), and reports per way the host
time of the tick (``put`` + sampling) and, measured directly, **the wait after the program's
end**: once ``put(greedy=True)`` has returned the program is over, and the step's two outputs
(kept by a spy on ``_get_step``) are fetched one after the other.  That is the transfer PERF.md
section 5 (PR 33) inferred as "not explained by the host": 2.9-4.8 ms for Moonlight's 10.5 MB.
It gates nothing and nothing here is imported by the program.

    python3 tools/chip_calls/pr34_fetch_ways.py [--ticks 24] [--seed 3400000001] [--rehearse]

``--rehearse``: the CPU rehearsal at the tiny sizes of ``benchmark/tests`` (control flow and
token equality only: no time from it is a device number).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)

CELLS = ("serve-moonlight-longdoc-closed64", "serve-mistral7b-longprompt-closed")
PROMPT = 256            # tokens every decoding row holds when the ticks start
CHUNKS = 3              # ticks a long prompt takes; its last chunk drains it


def _engine(cfg, seed):
    from benchmark.lib import spec
    from benchmark.runners import serve_ragged
    from deepspeed_tpu.inference.v2 import InferenceEngineV2, RaggedInferenceEngineConfig

    family = spec.module("families", cfg["family"])
    sv = cfg["serve"]
    return InferenceEngineV2(
        family.serve_model(cfg, int(sv["block_size"])), serve_ragged.make_params(family, cfg, seed),
        RaggedInferenceEngineConfig.from_dict({
            "state_manager": {"max_ragged_batch_size": sv["token_budget"],
                              "max_ragged_sequence_count": sv["max_ragged_sequence_count"],
                              "max_context": sv["max_context"]},
            "kv_cache": {"block_size": sv["block_size"], "num_blocks": sv["kv_pool_blocks"]}}))


def _spy_outputs(engine, poll):
    """Keep the (logits, next_tokens) of the last ragged step dispatched; while ``poll`` holds a
    true value, spin on the token vector's ``is_ready()`` before the dispatch returns."""
    last = []
    real = engine._get_step

    def get_step(bucket, tile=None):
        step = real(bucket, tile)

        def run(*args):
            out = step(*args)
            last[:] = out[:2]
            while poll and not out[1].is_ready():
                pass
            return out
        run.__name__ = step.__name__
        return run

    engine._get_step = get_step
    return last


def _serve(engine, cfg, seed, ticks, greedy, last):
    """The seeded script of ``ticks`` mixed ticks; returns (tokens handed out a tick, per-tick
    milliseconds by name)."""
    import jax
    import numpy as np

    from deepspeed_tpu.serving import SamplingParams, sample_batch

    sv, vocab = cfg["serve"], int(cfg["vocab_size"])
    slots, budget = int(sv["max_ragged_sequence_count"]), int(sv["token_budget"])
    prompt = min(PROMPT, budget // 4)
    rng = np.random.default_rng([seed, 34])
    decoding = list(range(1, slots))
    feed = {}
    for i in range(0, len(decoding), 3):                    # three prompts a forward
        uids = decoding[i:i + 3]
        rows = engine.put(uids, [rng.integers(0, vocab, size=(prompt,)).tolist() for _ in uids])
        feed.update({u: int(np.argmax(rows[u])) for u in uids})
    chunk = budget - len(decoding)
    params = [SamplingParams(greedy=True)] * slots
    handed, ms = [], {"tick": [], "sample": [], "logits_after_end": [], "tokens_after_end": []}
    for t in range(ticks):
        long_uid = 1000 + t // CHUNKS
        uids = decoding + [long_uid]
        chunks = [[feed[u]] for u in decoding] + [rng.integers(0, vocab, size=(chunk,)).tolist()]
        t0 = time.perf_counter()
        out = engine.put(uids, chunks, greedy=greedy)
        t1 = time.perf_counter()
        if greedy:
            toks = out
            # the program is over: what does each of its outputs cost to bring over now?
            a = time.perf_counter()
            jax.device_get(last[0])
            b = time.perf_counter()
            jax.device_get(last[1])
            c = time.perf_counter()
            ms["logits_after_end"].append((b - a) * 1e3)
            ms["tokens_after_end"].append((c - b) * 1e3)
        else:
            order = sorted(out)
            got = sample_batch(np.stack([out[u] for u in order]), params[:len(order)],
                               [0] * len(order), order)
            toks = dict(zip(order, got.tolist()))
        t2 = time.perf_counter()
        ms["tick"].append((t2 - t0) * 1e3)
        ms["sample"].append((t2 - t1) * 1e3 if not greedy else 0.0)
        handed.append(toks)
        feed.update({u: toks[u] for u in decoding})
        if (t + 1) % CHUNKS == 0 or t + 1 == ticks:        # its last chunk: the slot turns over
            engine.flush([long_uid])
    engine.flush(decoding)
    return handed, ms


def _median(xs):
    return round(statistics.median(xs), 3) if xs else None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=24)
    ap.add_argument("--seed", type=int, default=3400000001)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax

    from benchmark.lib import device, spec

    bench = spec.benchmark_spec()
    tiny = {}
    if args.rehearse:
        from benchmark.tests import rehearsal_sizes, test_rehearsal_moonlight
        tiny = {CELLS[0]: test_rehearsal_moonlight.TINY["config"],
                CELLS[1]: rehearsal_sizes.TINY[CELLS[1]]["config"]}
    device.claim_devices(1, allow_cpu=args.rehearse)
    device.enable_compile_cache()
    ok = True
    for cell in CELLS:
        cfg = {**spec.config_for(bench, spec.cell(bench, cell)), **tiny.get(cell, {})}
        engine = _engine(cfg, args.seed)
        poll = []
        last = _spy_outputs(engine, poll)
        # one tick a way first: the program compiles (or is read from the cache) outside the timing
        _serve(engine, cfg, args.seed, 1, False, last)
        ways = {}
        for name, greedy in (("logits", False), ("tokens", True), ("tokens_polled", True)) * 2:
            poll[:] = [True] if name == "tokens_polled" else []
            handed, ms = _serve(engine, cfg, args.seed, args.ticks, greedy, last)
            ways.setdefault(name, []).append((handed, ms))
        poll.clear()
        want = ways["logits"][0][0]
        same = all(h == want for runs in ways.values() for h, _ in runs)
        ok &= same
        sv = cfg["serve"]
        line = {
            "cell": cell, "device": jax.devices()[0].device_kind, "rehearsal": args.rehearse,
            "program": sorted({engine._steps[k].__name__ for k in engine.step_keys}),
            "logits_bytes": int(sv["max_ragged_sequence_count"]) * int(cfg["vocab_size"]) * 4,
            "ticks": args.ticks, "rows_compared": sum(len(h) for h in want),
            "tokens_equal": same,
            "first_difference": None if same else next(
                (t, u) for runs in ways.values() for h, _ in runs
                for t, (x, y) in enumerate(zip(h, want)) for u in y if x.get(u) != y[u]),
        }
        for name, runs in ways.items():
            for i, (_, ms) in enumerate(runs):
                line[f"{name}_{i}"] = {k: _median(v[1:]) for k, v in ms.items() if any(v)}
        print(json.dumps(line), flush=True)
        del engine, last, ways
        gc.collect()                # the step programs' closures hold the engine
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
