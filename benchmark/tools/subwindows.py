"""What a shorter window would have shown: the serving metrics
re-computed over the first T seconds of each run's window, from the side
files ``tools/measure.py`` keeps (every tick and request), then the spread
between runs at each T.  How the spread falls with the window's length is
what says whether a longer ``run_seconds`` would buy a tighter bound.

    python3 benchmark/tools/subwindows.py chiprun_out/<tag>/<cell>.s*r*.window_seed*.json -- 22.5 30 37.5 45

No JAX, no chip: arithmetic on recorded host-clock times.  ``out_tok_s``
counts a tick's tokens at the tick's end.
"""

from __future__ import annotations

import json
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _CHECKOUT)

from benchmark.lib import stats     # noqa: E402


def metrics(rec: dict, T: float) -> dict:
    toks = sum(n for at, _d, n, _u in rec["ticks"] if at < T)
    reqs = [r for r in rec["requests"] if r[7] and 0.0 <= r[2] < T]
    ttft = [1e3 * (r[4] - r[2]) for r in reqs if r[4] is not None]
    tpot = [1e3 * (r[5] - r[4]) / (r[6] - 1) for r in reqs
            if r[6] == r[1] and r[6] > 1]
    prompts = stats.prompt_tokens_between(
        [(r[0], r[3], r[4]) for r in rec["requests"] if r[4] is not None],
        0.0, T)
    # token gaps were not kept: the interval between tick ends, weighted by
    # the tokens the tick emitted, stands in for them (every running
    # sequence gets one token a tick)
    gaps = sorted((1e3 * (b[0] - a[0]), b[2]) for a, b in
                  zip(rec["ticks"], rec["ticks"][1:]) if b[0] < T)
    half, seen, itl = sum(n for _g, n in gaps) / 2.0, 0, None
    for g, n in gaps:
        seen += n
        if seen >= half:
            itl = g
            break
    return {"out_tok_s": toks / T, "total_tok_s": (toks + prompts) / T,
            "itl_p50_ms": itl,
            "ttft_p50_ms": stats.pct(ttft, 50),
            "tpot_p50_ms": stats.pct(tpot, 50)}


def main(argv) -> int:
    cut = argv.index("--")
    files, lengths = argv[:cut], [float(x) for x in argv[cut + 1:]]
    recs = []
    for p in sorted(files):
        with open(p) as f:
            recs.append(json.load(f))
    print(f"{len(recs)} runs")
    for T in lengths:
        rows = [metrics(r, T) for r in recs]
        for name in ("out_tok_s", "total_tok_s", "itl_p50_ms", "ttft_p50_ms",
                     "tpot_p50_ms"):
            v = [r[name] for r in rows if r[name] is not None]
            mid = stats.pct(v, 50)
            print(f"T {T:5.1f} s  {name:12s} " +
                  " ".join(f"{x:.4g}" for x in v) +
                  f"  median {mid:.4f}  spread "
                  f"{100 * (stats.spread(v) or 0):5.2f}%  range "
                  f"{100 * (min(v) / mid - 1):+.1f}% .. "
                  f"{100 * (max(v) / mid - 1):+.1f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
