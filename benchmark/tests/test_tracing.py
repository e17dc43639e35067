"""The trace reducer on hand-made intervals (and, in test_fixture.py, on a
trace recorded on the chip)."""

from benchmark.lib import tracing
from benchmark.lib.tracing import DeviceEvent, HostEvent, TraceView


def test_union_total_clip_subtract():
    u = tracing.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert u == [(0, 15), (20, 31)]
    assert tracing.total(u) == 26
    assert tracing.clip(u, 10, 25) == [(10, 15), (20, 25)]
    assert tracing.subtract([(0, 100)], [(10, 20), (30, 40)]) == \
        [(0, 10), (20, 30), (40, 100)]
    assert tracing.subtract([(0, 10), (20, 30)], [(5, 25)]) == \
        [(0, 5), (25, 30)]
    assert tracing.gaps([(10, 20)], 0, 30) == [(0, 10), (20, 30)]


def _ev(dev, name, start, dur, label=None):
    return DeviceEvent(dev, name, label or name, start, dur)


def test_busy_is_a_union_not_a_sum():
    v = TraceView([_ev(0, "fusion.1", 0, 100), _ev(0, "fusion.2", 50, 100),
                   _ev(0, "copy.1", 400, 100)], [])
    assert v.window() == (0, 500)
    assert v.busy(0) == [(0, 150), (400, 500)]
    assert v.busy_seconds() == 250 / 1e9


def test_busy_averages_over_devices():
    v = TraceView([_ev(0, "a", 0, 100), _ev(1, "a", 0, 300)], [])
    assert v.busy_seconds() == 200 / 1e9


FLASH = r" tpu_custom_call$"
PAGED = r"^paged_(prefill_|decode_|verify_)?attention[.\d]* .*tpu_custom_call"
GRID = r"^paged_attention[.\d]* .*tpu_custom_call"


def _hlo(dev, text, start, dur):
    return DeviceEvent(dev, text, tracing.label_of(text), start, dur)


def test_labels_are_cut_from_the_hlo_text():
    grid = ('%paged_attention.16 = bf16[1024,32,128]{2,1,0:T(8,128)(2,1)} '
            'custom-call(s32[1024]{0:T(1024)} %get-tuple-element.98, '
            'bf16[800,128,8,128]{3,2,1,0} %bitcast.1), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tracing.label_of(grid) == \
        "paged_attention.16 custom-call tpu_custom_call"
    ag = ('%all-gather-start.3 = (bf16[2048]{0}, bf16[4096]{0}) '
          'all-gather-start(bf16[2048]{0:T(1024)(128)(2,1)} %fusion.7), '
          'dimensions={0}')
    assert tracing.label_of(ag) == "all-gather-start.3 all-gather-start"
    # a fusion that CONSUMES a collective's result is not a collective
    fu = ('%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.2), '
          'kind=kLoop')
    assert tracing.label_of(fu) == "fusion.9 fusion"
    assert not tracing._COLLECTIVE.search(tracing.label_of(fu))
    assert tracing.label_of("bench/tick") == "bench/tick"


def test_kernel_sums_match_on_the_label():
    flash = ('%h_0.3 = (bf16[8,20,1024,64]{3,2,1,0}, f32[8,20,1024,8]{3,2,1,0})'
             ' custom-call(bf16[8,20,1024,64]{3,2,1,0} %bitcast.1208), '
             'custom_call_target="tpu_custom_call"')
    grid = ('%paged_attention.17 = bf16[256,32,128]{2,1,0} custom-call('
            's32[256]{0} %copy-done.38), custom_call_target="tpu_custom_call"')
    pre = ('%paged_prefill_attention.2 = bf16[256,32,128]{2,1,0} custom-call('
           's32[2]{0} %copy-done.8), custom_call_target="tpu_custom_call"')
    cat = ('%custom-call.40 = bf16[8]{0} custom-call(bf16[2]{0} %s.1), '
           'custom_call_target="ConcatBitcast"')
    v = TraceView([_hlo(0, flash, 0, 100), _hlo(0, grid, 200, 50),
                   _hlo(0, pre, 300, 30), _hlo(0, cat, 400, 5),
                   _hlo(0, "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p.1)",
                        500, 70)], [])
    assert v.seconds_matching(FLASH) == 180 / 1e9     # every Mosaic call
    assert v.seconds_matching(PAGED) == 80 / 1e9
    assert v.seconds_matching(GRID) == 50 / 1e9
    assert v.top_ops(2) == [
        ["h_ [tpu_custom_call] bf16[8,20,1024,64]", 100 / 1e9],
        ["fusion f32[4]", 70 / 1e9]]


def test_async_collectives_count_from_start_to_done():
    # all-gather in flight 0..100 on the async line; compute 0..70; the
    # -done op waits 70..100 on the op line: 30 exposed
    ops = [_ev(0, "fusion.1", 0, 70),
           _ev(0, "all-gather-done.1", 70, 30, "all-gather-done.1 all-gather-done")]
    asyn = [_ev(0, "all-gather-start.1", 0, 100,
                "all-gather-start.1 all-gather-start")]
    every, exposed = TraceView(ops, [], asyn).collective_seconds()
    assert every == 100 / 1e9 and exposed == 30 / 1e9


def test_exposed_collective_arithmetic():
    # all-gather 0..100 with compute over 0..60: 40 exposed;
    # all-reduce 200..300 fully under a fusion: 0 exposed
    v = TraceView([_ev(0, "all-gather.1", 0, 100), _ev(0, "fusion.1", 0, 60),
                   _ev(0, "all-reduce.2", 200, 100),
                   _ev(0, "fusion.2", 150, 200)], [])
    every, exposed = v.collective_seconds()
    assert every == 200 / 1e9 and exposed == 40 / 1e9


def test_idle_gaps_take_the_innermost_host_span():
    v = TraceView([_ev(0, "a", 0, 100), _ev(0, "b", 300, 100),
                   _ev(0, "c", 1000, 100)], [])
    labels = [("bench/tick", 50, 500), ("tick/sample", 150, 290),
              ("bench/idle_wait", 600, 900)]
    got = dict((k, s) for k, s in v.idle_gaps(labels))
    # gap 100..300 (mid 200) -> tick/sample, the shortest span containing
    # it; gap 400..1000 (mid 700) -> bench/idle_wait
    assert got == {"tick/sample": 200 / 1e9, "bench/idle_wait": 600 / 1e9}


def test_host_named_sorts_by_start():
    v = TraceView([], [HostEvent("t", "bench/tick", 50, 10),
                       HostEvent("t", "bench/tick", 5, 10),
                       HostEvent("t", "other", 0, 1)])
    assert [e.start for e in v.host_named(r"^bench/tick$")] == [5, 50]
