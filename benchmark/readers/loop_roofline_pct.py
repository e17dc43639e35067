"""Share of a peak the ticks of one kind reach in a looped model: what their
step programs had to read (``what: hbm``: ``costs_loop.decode_tick_bytes``,
against the HBM peak) or to compute (``what: flops``:
``costs_loop.tick_flops``, against the bf16 peak), summed over the WHOLE
executions of the traced stretch that ticks of ``kind`` launched, over the
device-busy time of those executions.

Executions are joined to launches as ``launch_device_ms_tick`` does
(``readers/_launches.py``: by program name and order, anchored in time; the
stretch's first and last execution left out).  What a launch asked for is the
program's own count on its dispatch span (``engine/decode_step`` /
``engine/ragged_step``, which a model that states ``kv_passes`` closes with
``loop_tokens``, ``loop_seqs``, ``loop_ctx_tokens`` and ``loop_attn_pairs``).  None without a
trace, peaks or a launch record, and when no dispatch span carries those
counters (a program without a looped stack).  args: kind, what (hbm|flops)."""

from benchmark.lib import costs_loop
from benchmark.readers import _host_labels, _launches

_COUNTERS = ("loop_tokens", "loop_seqs", "loop_ctx_tokens",
             "loop_attn_pairs")


def asked(facts):
    """{launch number: its dispatch span's counters}."""
    out = {}
    for r in _host_labels.tracer_spans(facts):
        a = r.get("attrs") or {}
        if r["name"] in _launches.DISPATCH and "launch" in a \
                and all(k in a for k in _COUNTERS):
            out[int(a["launch"])] = a
    return out


def read(facts, args, ctx):
    shapes = facts.get("shapes") or {}
    if ctx.peaks is None or "loop_matmul_params" not in shapes:
        return None
    execs, _info = _launches.joined(facts)
    counters = asked(facts)
    if not execs or not counters:
        return None
    kinds = set(args["kind"].split("+"))
    need = secs = 0.0
    n = 0
    for x in execs:
        row = x["launch"]
        if x["cut"] or row is None or row["kind"] not in kinds \
                or row["launch"] not in counters or x["busy"] <= 0:
            continue
        a = counters[row["launch"]]
        need += costs_loop.decode_tick_bytes(shapes, int(a["loop_ctx_tokens"])) \
            if args["what"] == "hbm" else costs_loop.tick_flops(
                shapes, int(a["loop_tokens"]), int(a["loop_seqs"]),
                int(a["loop_attn_pairs"]))
        secs += x["busy"] / 1e9
        n += 1
    if not n:
        return None
    peak = ctx.peaks["hbm_bytes_per_s" if args["what"] == "hbm"
                     else "bf16_flops_per_s"]
    ctx.log(f"looped {args['kind']} ticks: {n} whole executions asked "
            f"{need / n / 1e9:.3f} G{'B' if args['what'] == 'hbm' else 'FLOP'}"
            f" each in {1e3 * secs / n:.3f} ms busy")
    return 100.0 * need / secs / peak
