"""PR 23: spans inside the scheduler tick, counters on the tick span, and
kernel names on every Mosaic call.

* the span tree of a mixed and of a pure-decode tick: every ``engine/*``,
  ``fetch`` and ``advance`` span has a chain of parents up to its ``tick``,
  with the names and counters of the catalogue in
  ``observability/tracer.py``; the export passes ``obs_dump.validate_trace``;
* the tick span's closing counters;
* with no tracer, or a disabled one, a tick records nothing and builds no
  ``SpanHandle``;
* every ``pl.pallas_call`` of the package lowers for the TPU with
  ``kernel_metadata`` naming its kernel function.
"""

import contextlib
import functools
import importlib
import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.analysis import registry
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceEngineConfig)
from deepspeed_tpu.inference.v2.model_implementations import RaggedLlama
from deepspeed_tpu.models import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.observability import Tracer, tracer as tracer_mod
from deepspeed_tpu.serving import (ContinuousBatchScheduler, SamplingParams,
                                   SpeculativeConfig)

CFG = LlamaConfig.tiny(dtype=jnp.float32)
_TOOLS = pathlib.Path(__file__).resolve().parents[2] / "tools"


@pytest.fixture(scope="module")
def params():
    return LlamaForCausalLM(CFG).init(
        jax.random.key(0), np.zeros((1, 4), np.int32))["params"]


def _sched(params, tracer=None, budget=32, seqs=4, blocks=17, **kw):
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": budget,
                          "max_ragged_sequence_count": seqs,
                          "max_context": 48},
        "kv_cache": {"block_size": 8, "num_blocks": blocks}})
    return ContinuousBatchScheduler(
        InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg), tracer=tracer,
        **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=(n,)).tolist()


def _drive(sched):
    """A prefill-only tick, a pure-decode tick, a mixed tick (the second
    prompt arrives while the first decodes), then decode to the end: the
    last ticks decode one sequence alone, inside one KV block."""
    sched.submit(_prompt(13), SamplingParams(greedy=True, max_new_tokens=8))
    sched.step()
    sched.step()
    sched.submit(_prompt(11, 1), SamplingParams(greedy=True,
                                                max_new_tokens=3))
    sched.run_until_idle()


@pytest.fixture(scope="module")
def traced(params):
    tr = Tracer()
    sched = _sched(params, tracer=tr)
    _drive(sched)
    return tr, sched


def _ticks(tr):
    """[(tick record, {name: [descendant records]})], oldest first."""
    recs = [r for r in tr.records() if r["ph"] == "X"]
    by_id = {r["span_id"]: r for r in recs}
    out = {r["span_id"]: (r, {}) for r in recs if r["name"] == "tick"}
    for r in recs:
        up = r
        while up.get("parent") is not None:
            up = by_id[up["parent"]]
        if up is not r and up["span_id"] in out:
            out[up["span_id"]][1].setdefault(r["name"], []).append(r)
    return list(out.values())


def _of_kind(tr, kind):
    return [(t, kids) for t, kids in _ticks(tr)
            if t["attrs"].get("kind") == kind]


# --------------------------------------------------------------------- #
# the span tree
# --------------------------------------------------------------------- #
def test_every_inner_span_chains_up_to_a_tick(traced):
    tr, _ = traced
    recs = [r for r in tr.records() if r["ph"] == "X"]
    by_id = {r["span_id"]: r for r in recs}
    inner = [r for r in recs if r["name"].startswith("engine/")
             or r["name"] in ("fetch", "advance")]
    assert inner
    for r in inner:
        chain = [r]
        while chain[-1].get("parent") is not None:
            chain.append(by_id[chain[-1]["parent"]])
        names = [c["name"] for c in chain]
        assert names[-1] == "tick", names
        # under the tick's phase, never directly under the tick
        assert names[-2] in ("prefill", "decode", "verify", "sample",
                             "retire"), names
        assert {c["trace_id"] for c in chain} == {r["trace_id"]}
        assert all(a["t0_ns"] >= b["t0_ns"] and a["t1_ns"] <= b["t1_ns"]
                   for a, b in zip(chain, chain[1:]))


def _retired(kids, by_id):
    """``kids`` of a tick without what lies under its ``retire`` span (a
    decode tick that first retires the ragged step in flight: the tick
    after a mixed one), and that part: (kids, [fetch, advance] or [])."""
    if "retire" not in kids:
        return kids, []
    (top,), under = kids["retire"], []
    rest = {}
    for name, recs in kids.items():
        if name == "retire":
            continue
        mine = [r for r in recs if r["parent"] == top["span_id"]]
        under += mine
        rest[name] = [r for r in recs if r not in mine]
    return rest, sorted(under, key=lambda r: r["t0_ns"])


def test_pure_decode_tick_tree(traced):
    tr, _ = traced
    ticks = _of_kind(tr, "decode")
    assert ticks
    all_by_id = {r["span_id"]: r for r in tr.records()}
    retiring = 0
    for tick, kids in ticks:
        # the tick after a mixed one: the ragged step is retired first, its
        # fetch naming its launch, before anything is packed
        kids, first = _retired(kids, all_by_id)
        if first:
            retiring += 1
            assert [r["name"] for r in first] == ["fetch", "advance"]
            assert set(first[0]["attrs"]) == {"launch"}
            assert first[1]["t1_ns"] <= kids["pack"][0]["t0_ns"]
        # every pure-decode tick: one pack, one decode phase, one fetch and
        # one advance; a dispatch (prep + step) for each program it hands
        # the device: the step it returns unless that was in flight, and
        # the step after it when it runs ahead
        assert {"advance", "decode", "fetch", "pack"} <= set(kids) <= \
            {"advance", "decode", "engine/decode_prep", "engine/decode_step",
             "fetch", "pack"}
        assert all(len(kids[k]) == 1
                   for k in ("advance", "decode", "fetch", "pack"))
        preps = kids.get("engine/decode_prep", [])
        steps = kids.get("engine/decode_step", [])
        assert len(preps) == len(steps) <= 2
        counters = kids["decode"][0]["attrs"]
        assert set(counters) == {"ahead", "steps", "read_blocks"} \
            and counters["steps"] == 1
        # a step in flight is not dispatched again; one that is not, is;
        # beside it, at most the step after it
        assert (counters["ahead"] == 0) <= len(steps) <= \
            2 - counters["ahead"]
        # a counter is recorded once, where something reads it: the live
        # rows of each step on its engine/decode_prep (``gmm_roofline_pct``
        # of the benchmark reads it), the mechanism's on the decode span,
        # what the tick left waiting on ``pack`` (nobody, in ``_drive``)
        assert {k for k, v in kids.items() if "attrs" in v[0]} == \
            {"decode", "fetch", "pack"} | (
                {"engine/decode_prep", "engine/decode_step"} if preps
                else set())
        assert kids["pack"][0]["attrs"] == {"queued": 0}
        for prep in preps:
            assert set(prep["attrs"]) == {"seqs"} and \
                1 <= prep["attrs"]["seqs"] <= 4
        order = [r for pair in zip(preps, steps) for r in pair] + \
            [kids["fetch"][0], kids["advance"][0]]
        assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(order, order[1:]))
        assert all(all_by_id[r["parent"]]["name"] == "decode" for r in order)
    assert retiring == 1


def test_decode_span_counts_the_steps_ahead(traced):
    """``_drive``: r1 decodes alone (a step, and the next dispatched ahead),
    r2 arrives while that one is in flight (the tick prepares the mixed
    batch under it, returns its token and launches the batch on it), two
    rows decode until r2's third token (the tick that returns it sends the
    next step ahead over r1 alone: r2 ends by length with it), then r1
    alone to its eighth, which no step follows.  Every run of decode ticks
    hands the device one program a tick, but for the step that was in
    flight when the prompt arrived: a mixed tick returns it."""
    tr, _ = traced
    kinds = [t["attrs"]["kind"] for t, _ in _ticks(tr)]
    assert kinds == ["prefill", "decode", "mixed",
                     "decode", "decode", "decode", "decode"]
    ticks = _of_kind(tr, "decode")
    ahead = [kids["decode"][0]["attrs"]["ahead"] for _, kids in ticks]
    assert ahead == [0, 0, 1, 1, 1]
    dispatched = [len(kids.get("engine/decode_step", []))
                  for _, kids in ticks]
    assert dispatched == [2, 2, 1, 1, 0]
    assert sum(dispatched) == len(ticks) + 1 == 1 + \
        sum(kids["decode"][0]["attrs"]["steps"] for _, kids in ticks)
    # the table blocks (of 8) the returned step's rows hold at the
    # positions it fed: r1 at 13 | r1 at 16, 17 beside r2 at 11, 12 |
    # r1 at 18, 19
    assert [kids["decode"][0]["attrs"]["read_blocks"] for _, kids in ticks] \
        == [2, 3 + 2, 3 + 2, 3, 3]


def test_mixed_tick_tree(traced):
    """The mixed tick of ``_drive`` finds a decode step in flight: it
    builds its batch under that step, waits for it, launches the batch on
    its token and only then hands that token out."""
    tr, _ = traced
    (tick, kids), = _of_kind(tr, "mixed")
    # every row is greedy: the wait is for the token vector (``fetch``)
    assert sorted(kids) == ["advance", "engine/build_batch",
                            "engine/ragged_step", "engine/upload", "fetch",
                            "pack", "prefill", "sample"]
    assert all(len(v) == 1 for v in kids.values())
    # one decoding token and an 11-token prompt, padded to the 16 bucket;
    # the decoding row holds two table blocks of 8 up to its position
    assert kids["engine/build_batch"][0]["attrs"] == {
        "tokens": 1 + 11, "bucket": 16, "row_blocks": 2}
    # ... the launch record on the dispatch, the launch on the wait (the
    # decode step's, the launch before), on ``prefill`` that the batch was
    # prepared under the program before it, and on ``sample`` the rows
    # that emitted and how many by the program's argmax: the decoding row
    # of the step this tick returns
    assert {k for k, v in kids.items() if "attrs" in v[0]} == \
        {"engine/build_batch", "engine/ragged_step", "fetch", "pack",
         "prefill", "sample"}
    assert kids["pack"][0]["attrs"] == {"queued": 0}
    assert kids["prefill"][0]["attrs"] == {"ragged_steps": 1,
                                           "ragged_ahead": 1}
    assert kids["fetch"][0]["attrs"]["launch"] + 1 == \
        kids["engine/ragged_step"][0]["attrs"]["launch"]
    assert kids["sample"][0]["attrs"] == {"sampled": 1, "device_sampled": 1}
    by_id = {r["span_id"]: r for r in tr.records()}
    assert by_id[kids["advance"][0]["parent"]]["name"] == "sample"
    order = [kids[k][0] for k in (
        "pack", "engine/build_batch", "fetch", "engine/upload",
        "engine/ragged_step", "advance")]
    assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(order, order[1:]))
    assert all(by_id[r["parent"]]["name"] == "prefill" for r in order[1:5])


def _mixed_run(params, stochastic):
    """``_drive`` with the second request stochastic or not: (tracer,
    scheduler)."""
    tr = Tracer()
    sched = _sched(params, tracer=tr)
    sched.submit(_prompt(13), SamplingParams(greedy=True, max_new_tokens=8))
    sched.step()
    sched.step()
    sched.submit(_prompt(11, 1), SamplingParams(
        greedy=not stochastic, temperature=0.7, seed=5, max_new_tokens=3))
    sched.run_until_idle()
    return tr, sched


@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["all_greedy", "one_stochastic"])
def test_put_tick_wait_and_sample_counters(params, stochastic):
    """The wait of a ``put`` tick closes with the launch its dispatch
    closed with, under the name of what it fetched (``fetch``: the token
    vector; ``engine/fetch_logits``: the logits, one stochastic row is
    enough), and ``sample`` closes with the rows that emitted and how many
    of those tokens were the program's argmax."""
    tr, _ = _mixed_run(params, stochastic)
    put_ticks = [(t, kids) for t, kids in _ticks(tr)
                 if t["attrs"]["kind"] in ("mixed", "prefill")]
    assert [t["attrs"]["kind"] for t, _ in put_ticks] == ["prefill", "mixed"]
    for tick, kids in put_ticks:
        # the first tick packs the greedy request alone
        logits = stochastic and tick["attrs"]["kind"] == "mixed"
        wait, other = ("engine/fetch_logits", "fetch") if logits \
            else ("fetch", "engine/fetch_logits")
        assert other not in kids and len(kids[wait]) == 1
        # an all-greedy mixed tick finds the decode step of the tick before
        # in flight and builds its batch under it: its wait is for that
        # step, the launch before its own (the stochastic request is
        # admitted under that step too, but the batch is packed with it on
        # the host: that tick waits for its own launch)
        own = kids["engine/ragged_step"][0]["attrs"]["launch"]
        ahead = tick["attrs"]["kind"] == "mixed" and not stochastic
        assert kids[wait][0]["attrs"] == {"launch": own - ahead}
        # (the span around a ``put`` for logits closes with no counter)
        assert kids["prefill"][0].get("attrs") == (
            None if logits else {"ragged_steps": 1, "ragged_ahead": ahead})
        emitted = tick["attrs"]["emitted"]
        assert kids["sample"][0]["attrs"] == {
            "sampled": emitted, "device_sampled": 0 if logits else emitted}
    # a decode tick's ``fetch`` is the scheduler's own, no ``sample`` there
    for tick, kids in _of_kind(tr, "decode"):
        assert "sample" not in kids


def test_a_greedy_mixed_tick_after_a_logits_put_builds_nothing(params):
    """One program a ``(rows, tile)``: after a ladder of greedy requests
    the keys and names are what they were before the argmax moved in, and
    the first all-greedy mixed tick after a ``put`` for logits of the same
    bucket (the harness's ``_check_logits`` before its window) runs the
    program that ``put`` ran."""
    sched = _sched(params)
    eng = sched.engine
    for n in (9, 20):                               # the ladder: T16, T32
        sched.submit(_prompt(n), SamplingParams(greedy=True,
                                                max_new_tokens=2))
        sched.run_until_idle()
    keys = eng.step_keys
    assert keys == [(16, None), ("decode_step",), (32, None)]
    assert [eng._steps[k].__name__ for k in keys] == \
        ["ragged_step_T16", "decode_step", "ragged_step_T32"]
    row = eng.put([900], [_prompt(12, 3)])[900]     # logits, bucket 16
    assert row.shape == (CFG.vocab_size,)
    eng.flush([900])
    sizes = [eng._steps[k]._cache_size() for k in keys]
    tr = Tracer()
    sched.tracer = tr
    eng.attach_tracer(tr)
    _drive(sched)
    (_, kids), = _of_kind(tr, "mixed")
    assert kids["engine/build_batch"][0]["attrs"]["bucket"] == 16
    # (the token of the decode step the tick found in flight)
    assert kids["sample"][0]["attrs"] == {"sampled": 1, "device_sampled": 1}
    assert eng.step_keys == keys
    assert [eng._steps[k]._cache_size() for k in keys] == sizes == [1, 1, 1]


def test_the_emit_instant_is_gone(traced):
    tr, _ = traced
    assert not [r for r in tr.records() if r["name"] == "emit"]
    assert {r["name"] for r in tr.records() if r["ph"] == "i"} == \
        {"request/submit"}


def test_export_validates(traced):
    tr, _ = traced
    spec = importlib.util.spec_from_file_location("obs_dump",
                                                  _TOOLS / "obs_dump.py")
    obs_dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_dump)
    events = tr.export_events()
    assert obs_dump.validate_trace(events) == []
    tick = next(e for e in events if e["name"] == "tick")
    assert {"tick", "kind", "emitted"} <= set(tick["args"])


# --------------------------------------------------------------------- #
# the tick span's closing counters
# --------------------------------------------------------------------- #
def test_tick_closing_counters(traced):
    tr, sched = traced
    ticks = _ticks(tr)
    # the second prompt arrives while the step after tick 1 is in flight:
    # its batch is built under that step, by the tick that returns it
    assert [t["attrs"]["kind"] for t, _ in ticks[:4]] == \
        ["prefill", "decode", "mixed", "decode"]
    for t, kids in ticks:
        assert set(t["attrs"]) == {"tick", "kind", "emitted"}
        builds = kids.get("engine/build_batch", [])
        if t["attrs"]["kind"] == "decode":
            # one token a running sequence (and one more where the tick
            # first retired the mixed step before it), no ragged batch
            assert 1 <= t["attrs"]["emitted"] <= \
                sched.max_seqs * (1 + ("retire" in kids))
            assert not builds
        else:
            assert builds
            for b in builds:
                assert 0 < b["attrs"]["tokens"] <= b["attrs"]["bucket"]
    assert [t["attrs"]["tick"] for t, _ in ticks] == list(range(len(ticks)))
    # ... the mixed tick returns the decode step's one token, and the tick
    # after it the mixed step's two and its own decode step's two
    assert [t["attrs"]["emitted"] for t, _ in ticks[:4]] == [1, 1, 1, 2 + 2]
    assert ticks[0][1]["engine/build_batch"][0]["attrs"] == \
        {"tokens": 13, "bucket": 16, "row_blocks": 0}   # no one-token row
    assert sum(t["attrs"]["emitted"] for t, _ in ticks) == 8 + 3


def test_tiled_tick_is_one_forward_and_bucket_is_its_rows(params):
    """An engine whose budget is whole tiles (here 64 = 4 x 16): the
    scheduler sizes chunks by the ROWS ``can_schedule`` counts, so a tick
    whose chunks fill the tiled segment is one forward (the old packing
    pushed what alignment padding displaced into a second forward of the
    same ``put``), and ``bucket`` is the row count of the program that
    ran: ``max_seqs`` single-token rows + whole tiles."""
    tr = Tracer()
    cfg = RaggedInferenceEngineConfig.from_dict({
        "state_manager": {"max_ragged_batch_size": 64,
                          "max_ragged_sequence_count": 4,
                          "max_context": 64},
        "kv_cache": {"block_size": 8}})
    eng = InferenceEngineV2(RaggedLlama(CFG, 8), params, cfg)
    eng.PREFILL_TILE = 16
    sched = ContinuousBatchScheduler(eng, tracer=tr)
    sched.submit(_prompt(6), SamplingParams(greedy=True, max_new_tokens=12))
    sched.step()
    # 33 + 31 = 64 tokens are within the budget, but 33 takes three tiles
    # and leaves one: the second chunk is cut to 16 tokens
    for n, seed in ((33, 1), (31, 2)):
        sched.submit(_prompt(n, seed), SamplingParams(greedy=True,
                                                      max_new_tokens=2))
    sched.run_until_idle()
    forwards = [(t, kids) for t, kids in _ticks(tr)
                if "engine/ragged_step" in kids]
    assert [t["attrs"]["kind"] for t, _ in forwards[:3]] == \
        ["prefill", "mixed", "mixed"]
    for t, kids in forwards:
        assert len(kids["engine/ragged_step"]) == 1
        assert len(kids["engine/build_batch"]) == 1
    builds = [kids["engine/build_batch"][0]["attrs"] for _, kids in forwards]
    # ``row_blocks``: the table blocks of 8 the one-token rows hold (none in
    # the first forward; then the decoding prompt of 6 + what it generated,
    # and beside it the 33-token prompt's last token at position 32)
    # ``chunk_key_steps``: the key steps the tiled read's grid runs, over
    # the bucket's tiles and the layers: the table's 8 entries are one step
    # (the rule's four blocks of 128 are 64 of 8), so every tile with a
    # chunk holds one live step (``chunk_live_key_steps``)
    layers = CFG.num_hidden_layers

    def grid(tiles, live):
        return {"chunk_key_steps": layers * tiles,
                "chunk_live_key_steps": layers * live}

    assert builds[0] == {"tokens": 6, "bucket": 4 + 16, "row_blocks": 0,
                         **grid(1, 1)}
    assert builds[1] == {"tokens": 1 + 33 + 16, "bucket": 4 + 64,
                         "row_blocks": 1, **grid(4, 4)}
    assert builds[2] == {"tokens": 1 + 1 + 15, "bucket": 4 + 16,
                         "row_blocks": 1 + 33 // 8 + 1, **grid(1, 1)}
    assert {(b["bucket"], 16) for b in builds} == \
        {k for k in eng.step_keys if not isinstance(k[0], str)}
    # real tokens never pass the budget, rows never the largest program
    assert not eng.can_schedule([7, 8], [33, 31])
    assert eng.can_schedule([7, 8, 9, 10], [1, 1, 33, 16])
    assert not eng.can_schedule([7, 8], [64, 1])


def test_verify_tick_counters(params):
    tr = Tracer()
    sched = _sched(params, tracer=tr, speculative=SpeculativeConfig(draft_k=3))
    # a repeating prompt, so the n-gram drafter has something to propose
    sched.submit([5, 6, 7, 8] * 4, SamplingParams(greedy=True,
                                                  max_new_tokens=8))
    sched.run_until_idle()
    verify = _of_kind(tr, "verify")
    if not verify:
        pytest.skip("the drafter proposed nothing on this model")
    for tick, kids in verify:
        # a verify tick emits the accepted drafts and one token more
        assert 1 <= tick["attrs"]["emitted"] <= 3 + 1
        assert {"verify", "engine/verify_step", "fetch", "advance"} <= \
            set(kids)
    assert sum(t["attrs"]["emitted"] for t, _ in _ticks(tr)) == 8


# --------------------------------------------------------------------- #
# the launch record
# --------------------------------------------------------------------- #
_DISPATCH = ("engine/decode_step", "engine/ragged_step", "engine/verify_step")
_WAIT = ("fetch", "engine/fetch_logits")


def _launch_spans(tr):
    """(dispatch records, wait records), each oldest first by its end."""
    recs = sorted((r for r in tr.records() if r["ph"] == "X"),
                  key=lambda r: r["t1_ns"])
    return ([r for r in recs if r["name"] in _DISPATCH],
            [r for r in recs if r["name"] in _WAIT])


def test_every_dispatch_carries_its_launch_and_program(traced):
    tr, sched = traced
    eng = sched.engine
    dispatches, _ = _launch_spans(tr)
    for r in dispatches:
        assert set(r["attrs"]) == {"launch", "program"}
    # numbered from 1, engine-wide, no number twice, in dispatch order
    assert [r["attrs"]["launch"] for r in dispatches] == \
        list(range(1, len(dispatches) + 1))
    assert eng.last_launch == len(dispatches)
    # the name the jitted function was given, letter for letter the
    # ``jit(...)`` of the program's operations (and ``jit_...`` of its
    # module: ``test_step_programs_carry_their_names``)
    names = {eng._steps[key].__name__ for key in eng.step_keys}
    assert {r["attrs"]["program"] for r in dispatches} == names == \
        {"decode_step", "ragged_step_T16"}
    for r in dispatches:
        want = "decode_step" if r["name"] == "engine/decode_step" \
            else "ragged_step_T16"
        assert r["attrs"]["program"] == want
        assert f'"jit({want})/' in eng.lower_step(
            ("decode_step",) if want == "decode_step" else (16, None)
        ).as_text(debug_info=True)


def test_every_launch_is_retired_by_the_wait_that_names_it(traced):
    """Each launch is waited for once, by a ``fetch`` (a token vector: every
    row here is greedy) or an ``engine/fetch_logits`` (a ragged batch with
    a stochastic row: none here) that closes with its number;
    ``_drive`` ends idle, so no launch is left in flight.  A program sent
    ahead is retired in the NEXT tick: a decode step after that tick has
    (or has not) dispatched its successor, or, where a ragged batch was
    due, between that batch's build and its launch; the ragged step so
    launched before the next tick packs.  Every other launch is retired
    inside its own tick."""
    tr, _ = traced
    dispatches, waits = _launch_spans(tr)
    assert all(set(w["attrs"]) == {"launch"} for w in waits)
    retired = [w["attrs"]["launch"] for w in waits]
    assert sorted(retired) == [d["attrs"]["launch"] for d in dispatches]
    assert retired == sorted(retired)       # the device runs them in order
    wait_of = {w["attrs"]["launch"]: w for w in waits}
    tick_of, kids_of = {}, {}
    for tick, kids in _ticks(tr):
        kids_of[tick["attrs"]["tick"]] = kids
        for name in _DISPATCH + _WAIT:
            for r in kids.get(name, []):
                tick_of[r["span_id"]] = tick["attrs"]["tick"]
    by_launch = {d["attrs"]["launch"]: d for d in dispatches}
    ahead = []
    for n, d in by_launch.items():
        w = wait_of[n]
        assert d["t1_ns"] <= w["t0_ns"]
        assert w["name"] == "fetch"
        gap = tick_of[w["span_id"]] - tick_of[d["span_id"]]
        assert gap in (0, 1)
        if not gap:
            continue
        ahead.append(n)
        later = [x for x in dispatches
                 if tick_of[x["span_id"]] == tick_of[w["span_id"]]]
        if d["name"] == "engine/ragged_step":
            # the mixed step: retired before the next tick packs anything
            retire, = kids_of[tick_of[w["span_id"]]]["retire"]
            assert w["parent"] == retire["span_id"]
            assert all(w["t1_ns"] <= x["t0_ns"] for x in later)
            continue
        # a decode step: dispatched by the tick that returned the step
        # before it, before that tick waited for that one ...
        before = wait_of[n - 1]
        assert tick_of[before["span_id"]] == tick_of[d["span_id"]] and \
            d["t1_ns"] <= before["t0_ns"]
        ragged = [x for x in later if x["name"] == "engine/ragged_step"]
        if ragged:
            # ... and retired between the build and the launch of the
            # ragged batch that was due after it
            build, = kids_of[tick_of[w["span_id"]]]["engine/build_batch"]
            assert build["t1_ns"] <= w["t0_ns"] and \
                w["t1_ns"] <= ragged[0]["t0_ns"]
        else:
            # ... or after whatever the next tick dispatched
            assert all(x["t1_ns"] <= w["t0_ns"] for x in later)
    # the decode step in flight when the prompt arrived, the mixed step,
    # and the three decode ticks that found their step in flight
    # (``test_decode_span_counts_the_steps_ahead``: [0, 0, 1, 1, 1])
    assert ahead == [3, 4, 6, 7, 8]


def test_a_run_of_decode_ticks_retires_behind_its_successor(params):
    """Over a run of decode ticks of the same rows each tick dispatches
    the step after the one it returns and THEN waits for the one it
    returns: launch n is retired after launch n + 1 went out."""
    tr = Tracer()
    sched = _sched(params, tracer=tr)
    sched.submit(_prompt(9), SamplingParams(greedy=True, max_new_tokens=8))
    sched.run_until_idle()
    dispatches, waits = _launch_spans(tr)
    end_of = {d["attrs"]["launch"]: d["t1_ns"] for d in dispatches}
    behind = [w["attrs"]["launch"] for w in waits
              if end_of.get(w["attrs"]["launch"] + 1, 1 << 62) <= w["t0_ns"]]
    # prefill 1 (the first token) | decode: 2 and 3 out, 2 back | 4 out, 3
    # back | ... | 8 out, 7 back | 8 back: the eighth token's step is not
    # followed (the row reaches max_new_tokens with it)
    assert len(dispatches) == 8 and behind == [2, 3, 4, 5, 6, 7]


def test_a_finish_by_length_leaves_the_next_step_in_flight(params):
    """Two callers in a closed loop, one of them with short answers.  The
    decode tick that returns a row's last token closes its ``decode`` span
    with the step after it dispatched over the row that goes on, before it
    waited; the tick after it, with the caller's next request in the queue,
    is a mixed tick whose ``prefill`` span closes with ``ragged_ahead`` 1;
    and from the first such tick to the last nothing is compiled (the
    programs, the tokens' gather among them, are those of the ladder)."""
    from deepspeed_tpu.analysis.trace_guard import compile_count

    sched = _sched(params)
    sched.submit(_prompt(9), SamplingParams(greedy=True, max_new_tokens=3))
    sched.run_until_idle()                  # the ladder: T16, decode_step
    tr = Tracer()
    sched.tracer = tr
    sched.engine.attach_tracer(tr)

    def ask(n, seed, new):
        return sched.submit(_prompt(n, seed),
                            SamplingParams(greedy=True, max_new_tokens=new))

    short, long_ = ask(7, 1, 4), ask(6, 2, 40)
    finishes, compiled = [], None
    for seed in range(3, 7):
        while len(short.generated) < short.sampling.max_new_tokens - 1:
            sched.step()
        compiled = compile_count() if compiled is None else compiled
        sched.step()
        assert short.finish_reason == "length"
        finishes.append(sched._tick - 1)
        assert sched._inflight.packed == [long_]
        short = ask(8, seed, 3)
        sched.step()
    assert compile_count() == compiled
    sched.run_until_idle()
    ticks = {t["attrs"]["tick"]: (t, kids) for t, kids in _ticks(tr)}
    for n in finishes:
        tick, kids = ticks[n]
        assert tick["attrs"]["kind"] == "decode"
        assert kids["decode"][0]["attrs"]["steps"] == 1
        # the one dispatch of the tick: over the row that goes on, and out
        # before the wait for the step the tick returns (two rows)
        (prep,), (step,) = kids["engine/decode_prep"], \
            kids["engine/decode_step"]
        assert prep["attrs"]["seqs"] == 1
        assert step["t1_ns"] <= kids["fetch"][0]["t0_ns"]
        assert tick["attrs"]["emitted"] == 2
        tick, kids = ticks[n + 1]
        assert tick["attrs"]["kind"] == "mixed"
        assert kids["prefill"][0]["attrs"] == {"ragged_steps": 1,
                                               "ragged_ahead": 1}
        # the step over the survivor is retired between the batch's build
        # and its launch
        assert kids["engine/build_batch"][0]["t1_ns"] <= \
            kids["fetch"][0]["t0_ns"] <= kids["fetch"][0]["t1_ns"] <= \
            kids["engine/ragged_step"][0]["t0_ns"]
    assert sched.ragged_ahead_ticks == len(finishes) == 4


def test_a_tracer_attached_later_continues_the_count(params):
    """Launches are counted traced or not: a tracer attached to a running
    engine records from the number the engine has reached, and one taken
    off again leaves the count running."""
    sched = _sched(params)
    eng = sched.engine
    sched.submit(_prompt(9), SamplingParams(greedy=True, max_new_tokens=6))
    sched.step()
    sched.step()
    before = eng.last_launch
    assert before >= 2
    tr = Tracer()
    sched.attach_tracer(tr)
    sched.step()
    dispatches, waits = _launch_spans(tr)
    assert [d["attrs"]["launch"] for d in dispatches] == \
        list(range(before + 1, eng.last_launch + 1))
    # the step the untraced tick before left in flight is retired here,
    # under its own number
    assert [w["attrs"]["launch"] for w in waits] == [before]
    sched.attach_tracer(None)
    recorded, reached = len(tr), eng.last_launch
    sched.run_until_idle()
    assert len(tr) == recorded and eng.last_launch > reached


def test_a_sampled_decode_tick_fetches_its_own_launch(params):
    """A tick with a row that is not greedy dispatches one decode step and
    waits for its logits in the same tick: the ``fetch`` names the launch
    the tick's own ``engine/decode_step`` made."""
    tr = Tracer()
    sched = _sched(params, tracer=tr)
    sched.submit(_prompt(9), SamplingParams(greedy=False, temperature=0.8,
                                            max_new_tokens=4))
    sched.run_until_idle()
    decode = _of_kind(tr, "decode")
    assert decode
    for _tick, kids in decode:
        d, = kids["engine/decode_step"]
        w, = kids["fetch"]
        assert set(d["attrs"]) == {"launch", "program"}
        assert w["attrs"] == {"launch": d["attrs"]["launch"]}
        assert d["t1_ns"] <= w["t0_ns"]


def test_verify_dispatch_carries_the_launch_record(params):
    tr = Tracer()
    sched = _sched(params, tracer=tr, speculative=SpeculativeConfig(draft_k=3))
    sched.submit([5, 6, 7, 8] * 4, SamplingParams(greedy=True,
                                                  max_new_tokens=8))
    sched.run_until_idle()
    dispatches, waits = _launch_spans(tr)
    verify = [d for d in dispatches if d["name"] == "engine/verify_step"]
    if not verify:
        pytest.skip("the drafter proposed nothing on this model")
    for d in verify:
        assert re.fullmatch(r"verify_step_K[2-4]", d["attrs"]["program"])
        w, = [w for w in waits if w["attrs"]["launch"] == d["attrs"]["launch"]]
        assert w["name"] == "fetch" and d["t1_ns"] <= w["t0_ns"]
    assert [d["attrs"]["launch"] for d in dispatches] == \
        list(range(1, len(dispatches) + 1))



# --------------------------------------------------------------------- #
# a request's own spans: queued -> prefill -> decode, joined to launches
# --------------------------------------------------------------------- #
def _spy_launches(engine):
    """[(launch number, {uid: tokens fed}, late uids)] of every ragged
    step the engine launches (``put`` launches through it too)."""
    seen, real = [], engine.launch

    def launch(prepared, late_tokens=None):
        out = real(prepared, late_tokens)
        seen.append((out[2], dict(zip(prepared.scheduled,
                                      prepared.chunk_sizes)),
                     set(prepared.late)))
        return out

    engine.launch = launch
    return seen


def _greedy(sched):
    req = sched.submit(_prompt(13), SamplingParams(greedy=True,
                                                   max_new_tokens=4))
    sched.run_until_idle()
    return req, {}


def _stochastic(sched):
    req = sched.submit(_prompt(13), SamplingParams(
        greedy=False, temperature=0.7, seed=5, max_new_tokens=4))
    sched.run_until_idle()
    return req, {}


def _three_chunks(sched):
    req = sched.submit(_prompt(43), SamplingParams(greedy=True,
                                                   max_new_tokens=3))
    sched.run_until_idle()
    return req, {"chunks": 3}


def _behind_a_step_sent_ahead(sched):
    """Two callers; the short one ends by length, the tick that returns
    its last token sends the next decode step ahead over the other, and
    the caller's next request arrives behind that step."""
    short = sched.submit(_prompt(7, 1), SamplingParams(greedy=True,
                                                       max_new_tokens=4))
    sched.submit(_prompt(6, 2), SamplingParams(greedy=True,
                                               max_new_tokens=12))
    while short.finish_reason is None:
        sched.step()
    ahead = sched._inflight.launch
    assert ahead and not sched._inflight.ragged
    req = sched.submit(_prompt(8, 3), SamplingParams(greedy=True,
                                                     max_new_tokens=3))
    sched.run_until_idle()
    return req, {"behind_launch": ahead}


def _preempted_before_its_first_token(sched):
    req = sched.submit(_prompt(43), SamplingParams(greedy=True,
                                                   max_new_tokens=3))
    sched.step()                        # the first of three chunks
    sched._preempt(req)
    sched.run_until_idle()
    return req, {"chunks": 3, "preempted_after": 1}


class _StepClock:
    """``time`` for the modules that stamp a request: every read of the
    clock is a microsecond after the one before it, so the gap between two
    stamps counts the clock reads between them and not what else the
    machine was doing (six xdist workers on a loaded host stretched a
    gap between two adjacent stamps past a millisecond: PR 49's last run)."""

    def __init__(self):
        self.ns = 1_000_000_000

    def monotonic_ns(self):
        self.ns += 1_000
        return self.ns

    def monotonic(self):
        return self.monotonic_ns() / 1e9

    def __getattr__(self, name):
        import time

        return getattr(time, name)


@pytest.fixture
def step_clock(monkeypatch):
    from deepspeed_tpu.serving import request as request_mod
    from deepspeed_tpu.serving import scheduler as scheduler_mod

    clock = _StepClock()
    for mod in (tracer_mod, request_mod, scheduler_mod):
        monkeypatch.setattr(mod, "time", clock)
    return clock


@pytest.mark.parametrize("scenario, budget", [
    (_greedy, 32), (_stochastic, 32), (_three_chunks, 16),
    (_behind_a_step_sent_ahead, 32), (_preempted_before_its_first_token, 16)],
    ids=["greedy", "stochastic", "three_chunks", "behind_a_step_sent_ahead",
         "preempted_before_its_first_token"])
def test_request_span_chain(params, scenario, budget, step_clock):
    """One open phase a live request from submit to finish, under its own
    ``trace_id``: ``request/queued`` (submit -> admission), ``request/
    prefill`` (-> the first token handed out; closes with the step programs
    that carried its prompt) and ``request/decode`` (-> the end; closes
    with the tokens of the phase).  The phases join end to end, and they
    span ``first_token_time - arrival_time``."""
    tr = Tracer()
    sched = _sched(params, tracer=tr, budget=budget)
    launches = _spy_launches(sched.engine)
    req, want = scenario(sched)
    mine = sorted((r for r in tr.records() if r["trace_id"] == req.trace_id),
                  key=lambda r: r["t0_ns"])
    assert all(r.get("parent") is None for r in mine)
    submit, spans = mine[0], mine[1:]
    assert submit["name"] == "request/submit" and submit["ph"] == "i"
    assert submit["attrs"] == {"uid": req.uid,
                               "prompt_tokens": len(req.prompt)}
    names = [r["name"] for r in spans]
    again = ["request/queued", "request/prefill"] \
        if "preempted_after" in want else []
    assert names == again + ["request/queued", "request/prefill",
                             "request/decode"]
    # end to end: a phase opens where the one before it closed
    assert submit["t0_ns"] <= spans[0]["t0_ns"]
    for a, b in zip(spans, spans[1:]):
        assert 0 <= b["t0_ns"] - a["t1_ns"] < 1_000_000
    # ... from the submit to the first token, as the request itself
    # stamped them (on the injected clock: under a thousand clock reads
    # between two adjacent stamps, two thousand between the request's own
    # and the spans'; the request reads the clock when it is made and once
    # a tick before the tokens are handed out)
    queued, prefill, decode = spans[-3:]
    own = (req.first_token_time - req.arrival_time) * 1e9
    assert abs((decode["t0_ns"] - spans[0]["t0_ns"]) - own) < 2_000_000
    assert all("attrs" not in r for r in spans
               if r["name"] == "request/queued")
    # the prompt's chunks, from the engine's own launches: those that fed
    # this uid from the host until the prompt was in (after a preemption:
    # from the start again)
    feeds = [(number, sizes[req.uid]) for number, sizes, late in launches
             if req.uid in sizes and req.uid not in late]
    lost = want.get("preempted_after", 0)
    if lost:
        assert spans[1]["attrs"] == {
            "outcome": "preempted", "chunks": lost,
            "first_launch": feeds[0][0], "last_launch": feeds[lost - 1][0],
            "behind_launch": 0}
    last_run, fed = [], 0
    for number, n in feeds[lost:]:
        if fed < len(req.prompt):
            last_run.append(number)
            fed += n
    a = prefill["attrs"]
    assert set(a) == {"chunks", "first_launch", "last_launch",
                      "behind_launch"}
    assert a["chunks"] == len(last_run) == want.get("chunks", 1)
    assert (a["first_launch"], a["last_launch"]) == \
        (last_run[0], last_run[-1])
    assert a["first_launch"] <= a["last_launch"]
    assert a["behind_launch"] == want.get("behind_launch", 0)
    # every chunk's launch is a ragged step of the record, and the last
    # is retired by a wait that names it before the first token goes out
    dispatches, waits = _launch_spans(tr)
    ragged = {d["attrs"]["launch"] for d in dispatches
              if d["name"] == "engine/ragged_step"}
    assert set(last_run) <= ragged
    wait, = [w for w in waits if w["attrs"]["launch"] == a["last_launch"]]
    assert prefill["t0_ns"] <= wait["t1_ns"] <= decode["t0_ns"]
    if a["behind_launch"]:
        step, = [d for d in dispatches
                 if d["attrs"]["launch"] == a["behind_launch"]]
        assert step["name"] == "engine/decode_step"
        assert step["t1_ns"] <= prefill["t0_ns"]
    assert decode["attrs"] == {"tokens": len(req.generated) - 1,
                               "outcome": "finished", "reason": "length"}
    assert not sched._req_spans and not tr.open_spans()


def _held(tr):
    """The closing counters of every ``pack`` span that left someone
    waiting, oldest first."""
    return [r["attrs"] for r in tr.records() if r["name"] == "pack"
            and r["attrs"]["queued"]]


def _hold_budget(params, tr):
    """Two prompts of a whole budget each, submitted together: the first
    spends the tick's tokens."""
    sched = _sched(params, tracer=tr, budget=16)
    for seed in (0, 1):
        req = sched.submit(_prompt(16, seed), SamplingParams(
            greedy=True, max_new_tokens=2))
    return sched, req


def _two_decoding(params, tr, second_new, **kw):
    sched = _sched(params, tracer=tr, seqs=2, **kw)
    first = sched.submit(_prompt(9), SamplingParams(greedy=True,
                                                    max_new_tokens=12))
    second = sched.submit(_prompt(7, 1), SamplingParams(
        greedy=True, max_new_tokens=second_new))
    return sched, first, second


def _hold_rows(params, tr):
    """Both rows of a two-row engine decode and go on: the batch is full
    of them."""
    sched, _, second = _two_decoding(params, tr, 12)
    while len(second.generated) < 2:
        sched.step()
    return sched, sched.submit(_prompt(8, 2), SamplingParams(
        greedy=True, max_new_tokens=2))


def _hold_slots(params, tr):
    """The second row ends by length with the token in flight, so the
    batch packed under that step has a row to spare; but its request still
    holds its slot of the running set."""
    sched, _, second = _two_decoding(params, tr, 4)
    while len(second.generated) < 3:
        sched.step()
    assert sched._inflight is not None and second in sched._inflight.packed
    return sched, sched.submit(_prompt(8, 2), SamplingParams(
        greedy=True, max_new_tokens=2))


def _hold_kv(params, tr):
    """A pool of four usable blocks, all held by the first request."""
    sched = _sched(params, tracer=tr, blocks=5)
    first = sched.submit(_prompt(26), SamplingParams(greedy=True,
                                                     max_new_tokens=5))
    while not first.generated:
        sched.step()
    return sched, sched.submit(_prompt(9, 1), SamplingParams(
        greedy=True, max_new_tokens=2))


@pytest.mark.parametrize("make, rule", [
    (_hold_budget, "budget"), (_hold_rows, "rows"), (_hold_slots, "slots"),
    (_hold_kv, "kv")], ids=["budget", "rows", "slots", "kv"])
def test_pack_names_the_rule_that_held_a_request_back(params, make, rule):
    """``pack`` closes with ``queued``, the requests it left waiting, and
    ``held_by``, the first rule of ``_pack_prefills`` that held one back;
    a tick that leaves nobody waiting, or packs nothing new (it returns
    the step in flight), records ``queued`` alone."""
    tr = Tracer()
    sched, waiting = make(params, tr)
    before = len(_held(tr))
    sched.step()
    held = _held(tr)[before:]
    assert held and held[0] == {"queued": 1, "held_by": rule}
    sched.run_until_idle()
    assert all(r.finish_reason == "length" for r in sched.finished_requests)
    packs = [r["attrs"] for r in tr.records() if r["name"] == "pack"]
    assert packs[-1] == {"queued": 0}
    assert {a.get("held_by", rule) for a in packs if a["queued"]} == {rule}
    # a request's holds are the packs its ``request/queued`` span covers
    q = next(r for r in tr.records() if r["name"] == "request/queued"
             and r["trace_id"] == waiting.trace_id)
    covered = [r for r in tr.records() if r["name"] == "pack"
               and q["t0_ns"] <= r["t0_ns"] and r["t1_ns"] <= q["t1_ns"]]
    assert len(covered) == len(_held(tr)) >= 1


# --------------------------------------------------------------------- #
# off: nothing recorded, nothing built
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("make", [lambda: None,
                                  lambda: Tracer(enabled=False)],
                         ids=["no_tracer", "disabled_tracer"])
def test_untraced_tick_builds_no_span(params, monkeypatch, make):
    built = []
    real_init = tracer_mod.SpanHandle.__init__

    def counting(self, *a, **kw):
        built.append(a[0])
        real_init(self, *a, **kw)

    monkeypatch.setattr(tracer_mod.SpanHandle, "__init__", counting)
    tr = make()
    sched = _sched(params, tracer=tr)
    assert sched.engine.tracer is tr
    _drive(sched)
    # (the engine's constructor, once: on the process tracer)
    assert built == ["setup/engine_init"]
    # launches are still counted: one integer
    assert sched.engine.last_launch == 8
    if tr is not None:
        assert len(tr) == 0 and not tr.open_spans()
        assert tr.span("x") is tracer_mod._NULL_CM
    # once the programs are built, a tick leaves nothing on the process
    # tracer either and JAX reports nothing for the build listener to hear
    # (PR 64): a listener of the test's own, beside the program's, counts
    from jax._src import monitoring

    heard = []
    on_seconds = lambda event, *a, **kw: heard.append(event)
    on_event = lambda event, **kw: heard.append(event)
    monitoring.register_event_duration_secs_listener(on_seconds)
    monitoring.register_event_listener(on_event)
    try:
        assert tracer_mod._on_build_seconds in \
            monitoring.get_event_duration_listeners()
        assert tracer_mod._on_build_event in monitoring.get_event_listeners()
        written = tracer_mod.process_tracer()._n
        totals = tracer_mod.build_totals()
        _drive(sched)
    finally:
        monitoring.unregister_event_duration_listener(on_seconds)
        monitoring.unregister_event_listener(on_event)
    assert sched.engine.last_launch == 16
    assert heard == [] and tracer_mod.build_totals() == totals
    assert tracer_mod.process_tracer()._n == written


@pytest.mark.parametrize("make", [lambda: None,
                                  lambda: Tracer(enabled=False)],
                         ids=["no_tracer", "disabled_tracer"])
def test_untraced_request_path_keeps_nothing(params, make):
    """With no tracer, or a disabled one, a request that waits behind the
    budget, takes three chunks and is preempted on the way leaves nothing
    behind: no open phase, no record, and no attribute on the ``Request``
    beside its declared fields."""
    import dataclasses

    from deepspeed_tpu.serving.request import Request

    tr = make()
    sched = _sched(params, tracer=tr, budget=16)
    reqs = [sched.submit(_prompt(43, seed), SamplingParams(
        greedy=True, max_new_tokens=3)) for seed in (0, 1)]
    sched.step()
    assert not sched._req_spans
    sched._preempt(reqs[0])
    sched.run_until_idle()
    assert [r.finish_reason for r in reqs] == ["length"] * 2
    assert reqs[0].preemptions == 1 and not sched._req_spans
    fields = {f.name for f in dataclasses.fields(Request)}
    assert all(set(vars(r)) == fields for r in reqs)
    if tr is not None:
        assert len(tr) == 0 and not tr.open_spans()


def test_attach_tracer_reaches_the_engine(params):
    sched = _sched(params)
    assert sched.engine.tracer is None
    tr = Tracer()
    sched.attach_tracer(tr)
    assert sched.engine.tracer is tr
    _drive(sched)
    assert _of_kind(tr, "decode")
    sched.attach_tracer(None)
    assert sched.engine.tracer is None


def test_span_defaults_to_the_innermost_open_span():
    tr = Tracer(tid="t0")
    with tr.span("outer", trace_id="abc") as outer:
        with tr.span("inner") as inner:
            assert (inner.parent, inner.trace_id, inner.tid) == \
                (outer.span_id, "abc", "t0")
            with tr.span("other", trace_id="xyz", parent="p") as other:
                assert (other.parent, other.trace_id) == ("p", "xyz")
        with tr.span("second") as second:
            assert second.parent == outer.span_id
    with tr.span("alone") as alone:
        assert alone.parent is None
    # a span opened with start()/finish() is nobody's default parent
    h = tr.start("loose")
    with tr.span("after") as after:
        assert after.parent is None
    tr.finish(h)


def test_span_is_a_profiler_annotation_only_while_those_are_on(monkeypatch):
    entered = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(("in", self.name))

        def __exit__(self, *exc):
            entered.append(("out", self.name))

    monkeypatch.setitem(tracer_mod._PROFILER_CLS, "TraceAnnotation", Ann)
    tr = Tracer()
    with tr.span("quiet"):
        pass
    assert entered == []
    monkeypatch.setattr(tracer_mod, "_DEVICE_ANNOTATIONS", True)
    with tr.span("a"):
        with tr.span("b"):
            pass
    assert entered == [("in", "a"), ("in", "b"), ("out", "b"), ("out", "a")]
    # the Tracer span encloses its annotation: started before, ended after
    assert [r["name"] for r in tr.records()] == ["quiet", "b", "a"]
    # no tracer: the sites still annotate, and yield no SpanHandle
    with tracer_mod.open_span(None, "bare") as h:
        assert not isinstance(h, tracer_mod.SpanHandle)
    assert entered[-2:] == [("in", "bare"), ("out", "bare")]
    monkeypatch.setattr(tracer_mod, "_DEVICE_ANNOTATIONS", False)
    assert tracer_mod.open_span(None, "x") is tracer_mod._NULL_CM


def test_untraced_scheduler_still_annotates_while_those_are_on(
        params, monkeypatch):
    """With no tracer every site of the tick is the bare profiler
    annotation (an operator's ``jax.profiler`` capture shows the tick's
    brackets whoever holds a tracer), and the tick runs as untraced."""
    names = []

    class Ann:
        def __init__(self, name, **kw):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    monkeypatch.setitem(tracer_mod._PROFILER_CLS, "TraceAnnotation", Ann)
    monkeypatch.setitem(tracer_mod._PROFILER_CLS, "StepTraceAnnotation", Ann)
    monkeypatch.setattr(tracer_mod, "_DEVICE_ANNOTATIONS", True)
    sched = _sched(params)
    _drive(sched)
    assert len(sched.finished_requests) == 2
    assert {"setup/engine_init",
            "tick", "ds_tick", "pack", "prefill", "sample", "decode",
            "retire", "engine/build_batch", "engine/upload",
            "engine/ragged_step", "engine/decode_prep",
            "engine/decode_step", "fetch", "advance"} == set(names)


# --------------------------------------------------------------------- #
# kernel names on the device
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _lowered_calls(case_name):
    """[(kernel function's name, text lowered for the TPU)] of every
    ``pl.pallas_call`` a registered kernel case makes.  The call is
    lowered as the library configured it but for the interpreter flag;
    nothing runs (the case goes on with zeros)."""
    from jax.experimental import pallas as pl

    for mod in registry.KERNEL_MODULES:
        importlib.import_module(mod)
    real = pl.pallas_call
    calls = []

    def lowering_pallas_call(kernel, out_shape, **kw):
        kw["interpret"] = False
        inner = real(kernel, out_shape, **kw)
        fn = kernel
        while hasattr(fn, "func"):
            fn = fn.func

        def runner(*ops):
            sds = [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in ops]
            with jax.disable_jit(False):
                text = jax.jit(inner).trace(*sds).lower(
                    lowering_platforms=("tpu",)).as_text(debug_info=True)
            calls.append((fn.__name__, text))
            return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                out_shape)

        return runner

    pl.pallas_call = lowering_pallas_call
    try:
        with jax.disable_jit():
            _CASES.get(case_name, registry.KERNEL_CASES[case_name].fn)()
    finally:
        pl.pallas_call = real
    return calls


def _quantizer_case():
    """32 groups: the registered case's 64 make a rank-1 scale block of 32
    of 64, which this jax's Mosaic lowering refuses (a block of rank 1
    has to cover its array or be a multiple of 128)."""
    from deepspeed_tpu.ops import quantizer

    x = jnp.asarray(np.linspace(-1.0, 1.0, 32 * 512, dtype=np.float32))
    quantizer._quantize_kernel_call(quantizer._group(x, 32))


_CASES = {"quantizer_int8": _quantizer_case}


_KERNEL_ENTRIES = [
    ("flash_attention", "_fwd_kernel"),
    ("flash_attention", "_bwd_dq_kernel"),
    ("flash_attention", "_bwd_dkv_kernel"),
    ("flash_attention_folded", "_fwd_kernel_folded"),
    ("flash_attention_folded", "_bwd_dq_kernel_folded"),
    ("flash_attention_folded", "_bwd_dkv_kernel_folded"),
    ("paged_attention_grid", "_kernel"),
    ("paged_prefill", "_prefill_kernel"),
    ("paged_decode_dma", "_decode_kernel"),
    ("paged_verify_multiquery", "_verify_kernel"),
    ("paged_two_segment", "_decode_kernel"),
    ("paged_two_segment", "_prefill_kernel"),
    ("latent_decode_dma", "_latent_decode_kernel"),
    ("latent_expand_prefill", "_latent_expand_kernel"),
    ("latent_expand_prefill", "_latent_prefill_kernel"),
    ("sparse_tile_read", "_sparse_tile_read_kernel"),
    ("gmm_fwd", "_gmm_kernel"),
    ("gmm_dlhs", "_gmm_dlhs_kernel"),
    ("gmm_drhs", "_gmm_drhs_kernel"),
    ("gdn_step", "_gdn_step_kernel"),
    ("gdn_chunk", "_gdn_chunk_kernel"),
    ("ssm_step", "_ssm_step_kernel"),
    ("ssm_chunk", "_ssm_chunk_kernel"),
    ("ssd_step", "_ssd_step_kernel"),
    ("ssd_chunk", "_ssd_chunk_kernel"),
    ("quantized_matmul", "_qmm_kernel"),
    ("quantizer_int8", "_quantize_kernel"),
    ("block_sparse_attention", "_fwd_kernel"),
    ("block_sparse_attention", "_bwd_dq_kernel"),
    ("block_sparse_attention", "_bwd_dkv_kernel"),
    ("evoformer_attn", "_evo_kernel"),
]


@pytest.mark.parametrize("case, kernel", _KERNEL_ENTRIES,
                         ids=[f"{c}-{k}" for c, k in _KERNEL_ENTRIES])
def test_pallas_call_lowers_with_its_kernels_name(case, kernel):
    calls = _lowered_calls(case)
    mine = [(n, t) for n, t in calls if n == kernel
            or (kernel.startswith("_fwd_kernel") and n == kernel + "_onepass")]
    assert mine, (kernel, sorted({n for n, _ in calls}))
    for name, text in mine:
        assert "tpu_custom_call" in text
        m = re.search(r'kernel_metadata = "(.*?)"\}', text, re.S)
        assert m, "no kernel_metadata on the custom call"
        # MLIR escapes a byte of a string attribute as a backslash and
        # two hex digits
        meta = re.sub(r"\\([0-9A-F]{2})",
                      lambda h: chr(int(h.group(1), 16)), m.group(1))
        assert re.sub(r"\s", "", meta) == '{"kernel":"%s"}' % name
        # the Mosaic kernel keeps the function's name too
        assert f'kernel_name = "{name}"' in text


def test_every_pallas_call_site_is_covered():
    """No ``pallas_call`` without the names: each site passes
    ``**kernel_names(...)``."""
    root = pathlib.Path(__file__).resolve().parents[2] / "deepspeed_tpu"
    sites = named = 0
    for mod in registry.KERNEL_MODULES:
        src = (root.parent / (mod.replace(".", "/") + ".py")).read_text()
        sites += len(re.findall(r"pl\.pallas_call\(", src))
        named += len(re.findall(r"\*\*kernel_names\(", src))
    assert sites == named == 29


def test_paged_wrappers_keep_their_instruction_names():
    """The accepted readers match ``paged_*attention`` at the start of the
    Mosaic call's HLO instruction name, which XLA takes from the jitted
    wrapper while no ``name=`` is passed: the four paged kernels pass
    metadata only."""
    for case in ("paged_attention_grid", "paged_prefill", "paged_decode_dma",
                 "paged_verify_multiquery", "paged_two_segment"):
        for name, text in _lowered_calls(case):
            loc = re.search(r'loc\("([^"]*pallas_call)"', text)
            assert loc and f"{name}/pallas_call" not in loc.group(1), loc
    # ... and the others pass name= too, which reaches the op_name
    (name, text), = _lowered_calls("quantized_matmul")
    assert re.search(r'loc\("[^"]*_qmm_kernel/pallas_call"', text)


def test_step_programs_carry_their_names(traced):
    """Each jitted step program is named for what it is: the name heads
    every ``op_name`` of a trace."""
    _, sched = traced
    eng = sched.engine
    got = {key: re.search(r"module @(\w+)", eng.lower_step(key).as_text())
           .group(1) for key in eng.step_keys}
    assert got[("decode_step",)] == "jit_decode_step"
    assert got[(16, None)] == "jit_ragged_step_T16"
    assert set(got.values()) <= {"jit_decode_step", "jit_ragged_step_T16",
                                 "jit_ragged_step_T32"}
    text = eng.lower_step(("decode_step",)).as_text(debug_info=True)
    for scope in ("layers_0/attn/qkv", "layers_0/attn/rope_insert",
                  "layers_1/mlp", "lm_head", "sample_argmax", "embed"):
        assert f'"jit(decode_step)/{scope}' in text, scope
    # off the TPU the decode read is the XLA gather composition
    assert "layers_0/attn/gather_read" in text or \
        "layers_0/attn/dense_read" in text


def test_engines_key_the_compile_cache_on_names(params):
    """The persistent compile cache's default key strips debug information,
    so a program that differs from a cached one only in its scopes would
    load the old executable and show the old names: building either engine
    puts names and locations into the key."""
    import sys

    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups

    sys.path.insert(0, str(pathlib.Path(__file__).parent))
    from simple_model import SimpleModel

    flag = "jax_compilation_cache_include_metadata_in_key"
    model = SimpleModel(hidden_dim=8)
    for build in (lambda: _sched(params),
                  lambda: deepspeed_tpu.initialize(
                      model=(model.init, model.apply),
                      config={"train_micro_batch_size_per_gpu": 2,
                              "optimizer": {"type": "Adam",
                                            "params": {"lr": 1e-2}}})):
        jax.config.update(flag, False)
        build()
        assert getattr(jax.config, flag) is True
    groups.reset()
