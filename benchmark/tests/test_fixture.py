"""The reducer on traces recorded on the chip in PR 22 (TPU v5 lite, jax
0.9.0) and cut down to a stretch each so the tree stays small:

* ``train_gpt2_d64_1chip`` — the first 45 ms of the traced steps of
  ``train-gpt2large-d64-s1k`` (part of one step: 1516 operations);
* ``serve_chat_1chip`` — 480 ms of ``serve-mistral7b-chat-steady``: a mixed
  tick on the 256 bucket, a pure-decode tick, a mixed tick on the 1024
  bucket (3126 operations).

Only the event lists of the device's "XLA Ops" / "Async XLA Ops" lines and of
the Python thread were kept (no per-event statistics).  One TPU core runs its
operations one after another, so on these traces the union of intervals must
equal the plain sum of durations — an independent check of the union."""

import os

import pytest

from benchmark.lib import tracing

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
FLASH = r" tpu_custom_call$"
GRID = r"^paged_attention[.\d]* .*tpu_custom_call"


@pytest.fixture(scope="module")
def train():
    return tracing.TraceView.from_xplane(
        os.path.join(HERE, "train_gpt2_d64_1chip.xplane.pb"))


@pytest.fixture(scope="module")
def serve():
    return tracing.TraceView.from_xplane(
        os.path.join(HERE, "serve_chat_1chip.xplane.pb"))


def test_train_trace(train):
    assert train.devices == [0]
    assert len(train.device_events) == 1516
    assert len(train.async_events) == 570
    assert train.window() == (45452250, 90000437)
    assert sum(e.dur for e in train.device_events) == 41061428
    assert train.busy_seconds() == pytest.approx(0.041061428, rel=1e-9)
    # the flash kernels are the only Mosaic calls; they carry the flax
    # scope's name (h_0, h_1, ...)
    assert train.seconds_matching(FLASH) == pytest.approx(0.008297789)
    assert {e.label.split(".")[0] for e in train.matching(FLASH)} <= \
        {f"h_{i}" for i in range(14)}
    assert train.seconds_matching(GRID) == 0.0
    assert train.collective_seconds() == (0.0, 0.0)      # one chip
    assert train.top_ops(2)[1] == \
        ["h_ [tpu_custom_call] bf16[8,20,1024,64]", pytest.approx(0.008297789)]
    assert len(train.host_named(r"^bench/dispatch_step$")) == 6
    assert len(train.host_named(r"^bench/clock_sync$")) == 1


def test_serve_trace(serve):
    assert len(serve.device_events) == 3126
    assert sum(e.dur for e in serve.device_events) == 469197535
    assert serve.busy_seconds() == pytest.approx(0.469197535, rel=1e-9)
    assert serve.seconds_matching(GRID) == pytest.approx(0.367645287)
    top = serve.top_ops(2)
    assert top[0] == ["paged_attention [tpu_custom_call] bf16[1024,32,128]",
                      pytest.approx(0.260813904)]
    assert top[1] == ["paged_attention [tpu_custom_call] bf16[256,32,128]",
                      pytest.approx(0.106831383)]
    ticks = serve.host_named(r"^bench/tick$")
    assert len(ticks) == 4
    assert len(serve.host_named(r"^engine/decode_step$")) == 2
    assert len(serve.host_named(r"^engine/ragged_step$")) == 2


def test_idle_gaps_land_in_the_tick_that_holds_them(serve):
    lo, hi = serve.window()
    labels = [(e.name, e.start, e.end)
              for e in serve.host_named(r"^(bench/tick|engine/)")]
    gaps = serve.idle_gaps(labels)
    idle = (hi - lo) / 1e9 - serve.busy_seconds()
    assert sum(s for _n, s in gaps) == pytest.approx(idle, rel=1e-6)
    assert gaps[0][0] == "bench/tick"
