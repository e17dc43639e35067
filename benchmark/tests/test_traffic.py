"""The generator reproduces byte-identical traffic from a seed."""

import glob
import os

import numpy as np
import pytest

from benchmark.lib import spec, traffic

MIXES = sorted(os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(spec.BENCH_DIR, "traffic", "*.json")))


def _mix(name):
    return spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                       name + ".json"))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bytes_other_seed_other_bytes(name):
    mix = _mix(name)
    assert traffic.fingerprint(mix, 5) == traffic.fingerprint(mix, 5)
    assert traffic.fingerprint(mix, 5) != traffic.fingerprint(mix, 6)


def test_arrivals_are_sorted_inside_their_edges_with_a_fixed_count():
    mix = {"kind": "open_loop",
           "arrivals": {"rate_per_s": 20.0}}
    t = traffic.arrival_times(mix, 3, -5.0, 95.0)
    assert np.all(np.diff(t) >= 0) and t[0] >= -5.0 and t[-1] < 95.0
    assert (t < 0).sum() == 100 and (t >= 0).sum() == 1900
    u = traffic.arrival_times(mix, 4, -5.0, 95.0)
    assert len(u) == len(t) and not np.array_equal(t, u)


def test_uniform_lengths_cover_their_range_evenly():
    x = traffic.draw(np.random.default_rng(0),
                     {"dist": "uniform", "min": 32, "max": 96}, 13000,
                     block=1300)
    assert x.min() == 32 and x.max() == 96
    assert set(np.bincount(x)[32:]) == {200}        # 65 values, evenly
    with pytest.raises(ValueError):
        traffic.draw(np.random.default_rng(0), {"dist": "fixed"}, 4, block=4)


def test_lengths_respect_their_clips():
    rng = np.random.default_rng(0)
    d = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 2048}
    x = traffic.draw(rng, d, 20000, block=4000)
    assert x.min() >= 32 and x.max() <= 2048
    assert abs(np.median(x) - 256) < 12


def test_prompts_differ_between_requests_and_seeds():
    mix = {"kind": "open_loop", "stratify_block": 4,
           "arrivals": {"rate_per_s": 10.0},
           "prompt_tokens": {"dist": "uniform", "min": 64, "max": 64},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 4}}
    a, b = traffic.open_loop_plan(mix, 9, 0.0, 1.0)[:2]
    pa, pb = (traffic.prompt_tokens(9, r, 1000) for r in (a, b))
    assert len(pa) == len(pb) == 64 and pa != pb
    assert pa == traffic.prompt_tokens(9, a, 1000)
    assert pa != traffic.prompt_tokens(8, a, 1000)


def test_train_batches_are_fresh_and_ordered():
    mix = {"kind": "train_steps", "global_batch": 2, "seq_len": 16}
    a = traffic.train_batches(mix, 4, 100)
    b = traffic.train_batches(mix, 4, 100)
    x0, x1 = next(a), next(a)
    assert x0.shape == (2, 16) and x0.dtype == np.int32
    assert not np.array_equal(x0, x1)
    assert np.array_equal(x0, next(b)) and np.array_equal(x1, next(b))


def test_stratified_blocks_carry_the_same_multiset_in_another_order():
    d = {"dist": "lognormal", "median": 1024, "sigma": 0.6, "min": 512,
         "max": 4000}
    a = traffic.draw(np.random.default_rng(1), d, 32, block=16)
    b = traffic.draw(np.random.default_rng(2), d, 32, block=16)
    assert sorted(a[:16]) == sorted(a[16:]) == sorted(b[:16])
    assert list(a[:16]) != list(b[:16])
    assert abs(np.median(a) - 1024) < 60


def test_fixed_count_arrivals_offer_the_same_number_every_run():
    mix = {"kind": "open_loop", "stratify_block": 8,
           "arrivals": {"rate_per_s": 1.5},
           "prompt_tokens": {"dist": "uniform", "min": 10, "max": 90},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12}}
    plans = [traffic.open_loop_plan(mix, s, -10.0, 32.0) for s in (1, 2)]
    for plan in plans:
        due = np.asarray([r.due_s for r in plan])
        assert (due < 0).sum() == 15 and (due >= 0).sum() == 48
        assert np.all(np.diff(due) >= 0)
    w = [sorted(r.prompt_len for r in p if r.due_s >= 0) for p in plans]
    assert w[0] == w[1]                     # same work, other order
    assert [r.due_s for r in plans[0]] != [r.due_s for r in plans[1]]


def test_closed_loop_first_requests_are_staggered_by_the_seed():
    mix = {"kind": "closed_loop", "clients": 8, "start_stagger_s": 6.0,
           "prompt_tokens": {"dist": "uniform", "min": 10, "max": 90},
           "output_tokens": {"dist": "uniform", "min": 5, "max": 5}}
    a, b, c = (traffic.ClosedLoop(mix, s) for s in (7, 7, 8))
    assert a.first_due_s.shape == (8,)
    assert np.all((a.first_due_s >= 0) & (a.first_due_s < 6.0))
    assert np.array_equal(a.first_due_s, b.first_due_s)
    assert not np.array_equal(a.first_due_s, c.first_due_s)
    del mix["start_stagger_s"]
    assert not traffic.ClosedLoop(mix, 7).first_due_s.any()
    assert [a.next(i % 8).rid for i in range(12)] == list(range(12))


def test_closed_loop_lengths_are_fixed_and_only_tokens_follow_the_seed():
    mix = {"kind": "closed_loop", "clients": 8,
           "prompt_tokens": {"dist": "lognormal", "median": 1024,
                             "sigma": 0.6, "min": 512, "max": 4000},
           "output_tokens": {"dist": "uniform", "min": 32, "max": 96}}
    a, b = traffic.ClosedLoop(mix, 1), traffic.ClosedLoop(mix, 2)
    la = [a.next(c) for c in range(8)]
    lb = [b.next(c) for c in range(8)]
    assert [(r.prompt_len, r.output_len) for r in la] == \
        [(r.prompt_len, r.output_len) for r in lb]
    assert [r.prompt_len for r in la] == sorted(r.prompt_len for r in la)
    assert la[0].prompt_len == 512 and 2400 < la[7].prompt_len < 2700
    assert sorted(r.output_len for r in la) == [36, 44, 52, 60, 68, 76, 84, 92]
    assert a.next(3).prompt_len == la[3].prompt_len       # every time
    assert traffic.prompt_tokens(1, la[0], 1000) != \
        traffic.prompt_tokens(2, lb[0], 1000)
