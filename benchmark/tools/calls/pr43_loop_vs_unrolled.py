"""PR 43: the passes as a loop in the step program against the passes
written out in its text (``pr43_faults.fault("unrolled")``), on the cell's engine
at the cell's sizes, seeded weights, one process:

* seconds to the first result of each program (building it, or reading it
  from the compile cache: the log says which), the pure-decode program and
  the 8 + 256-row mixed program;
* milliseconds a pure-decode tick: 8 sequences of ``LIVE`` tokens each,
  ``STEPS`` ``decode_step`` calls chained on the device (each fed the one
  before's tokens), one ``block_until_ready`` at the end;
* milliseconds a mixed tick: 7 decoding rows beside one 256-token chunk
  (``put`` with ``sync=True``), the mean of ``MIXED`` of them.

    python3 benchmark/tools/calls/pr43_loop_vs_unrolled.py [looped|unrolled ...]
"""

import gc
import os
import sys
import time

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from benchmark.tools.calls import pr43_faults           # noqa: E402

LIVE, STEPS, MIXED = 300, 60, 6


def measure(cfg, family, unrolled: bool) -> None:
    name = "unrolled" if unrolled else "looped"
    vocab = int(cfg["vocab_size"])
    rng = np.random.default_rng(43)
    eng = pr43_faults.cell_engine(cfg, family, 4300000043)
    jax.block_until_ready(eng.params)
    uids = list(range(1, 9))
    t0 = time.monotonic()
    eng.put([uids[0]], [rng.integers(0, vocab, size=(LIVE,)).tolist()])
    print(f"{name}: first put (8 + 512 rows) {time.monotonic() - t0:.1f} s",
          flush=True)
    for uid in uids[1:]:
        eng.put([uid], [rng.integers(0, vocab, size=(LIVE,)).tolist()])
    tok = [1] * 8
    t0 = time.monotonic()
    _, nxt = eng.decode_step(uids, tok, greedy=True)
    jax.block_until_ready(nxt)
    print(f"{name}: first decode_step {time.monotonic() - t0:.1f} s",
          flush=True)
    for _ in range(5):
        _, nxt = eng.decode_step(uids, nxt, greedy=True)
    jax.block_until_ready(nxt)
    t0 = time.monotonic()
    for _ in range(STEPS):
        _, nxt = eng.decode_step(uids, nxt, greedy=True)
    jax.block_until_ready(nxt)
    ms = 1e3 * (time.monotonic() - t0) / STEPS
    print(f"{name}: pure-decode tick {ms:.3f} ms over {STEPS} steps, 8 rows "
          f"of ~{LIVE + STEPS // 2} tokens", flush=True)
    # mixed ticks: sequence 8 leaves, a 256-token chunk joins 7 decodes
    eng.flush([uids[-1]])
    took = []
    for i in range(MIXED + 1):
        new = 100 + i
        chunk = rng.integers(0, vocab, size=(256,)).tolist()
        t0 = time.monotonic()
        eng.put(uids[:-1] + [new], [[1]] * 7 + [chunk], greedy=True)
        took.append(time.monotonic() - t0)
        eng.flush([new])
    print(f"{name}: first mixed put (8 + 256 rows) {took[0]:.1f} s; mixed "
          f"tick {1e3 * float(np.mean(took[1:])):.3f} ms over {MIXED}",
          flush=True)
    mem = jax.devices()[0].memory_stats() or {}
    print(f"{name}: peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use', 0) / 1e9:.2f} GB", flush=True)
    del eng
    gc.collect()


def main(argv) -> int:
    from benchmark.lib import device, spec

    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, pr43_faults.CELL))
    device.claim_devices(1)
    print(f"compile cache {device.enable_compile_cache()}", flush=True)
    family = spec.module("families", cfg["family"])
    for which in argv or ["looped", "unrolled"]:
        with pr43_faults.fault("unrolled" if which == "unrolled"
                               else "clean"):
            measure(cfg, family, which == "unrolled")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
