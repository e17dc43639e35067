"""Share of a peak the ticks of one kind reach in a hybrid model (state
slots beside a KV cache): what their step programs had to move (``what:
hbm``: ``costs_hybrid.decode_tick_bytes``, STATE INCLUDED, against the HBM
peak) or to compute (``what: flops``: ``costs_hybrid.tick_flops``, against
the bf16 peak), summed over the WHOLE executions of the traced stretch that
ticks of ``kind`` launched, over the device-busy time of those executions.

Executions are joined to launches as ``launch_device_ms_tick`` and
``loop_roofline_pct`` do (``readers/_launches.py``: by program name and
order, anchored in time; the stretch's first and last execution left out).
What a launch asked for is the program's own count on its dispatch span
(``engine/decode_step`` / ``engine/ragged_step``, which an engine whose
model states both a ``state_spec`` and KV layers closes with ``hyb_seqs``,
``hyb_tokens``, ``hyb_ctx_tokens``, ``hyb_attn_pairs`` and
``hyb_state_seqs``).  None without a trace, peaks or a launch record, when
the family gives no ``gdn_conv_channels`` (not a hybrid this reader
counts), and when no dispatch span carries those counters (a program from
before them).  args: kind, what (hbm|flops)."""

from benchmark.lib import costs_hybrid
from benchmark.readers import _host_labels, _launches

_COUNTERS = ("hyb_seqs", "hyb_tokens", "hyb_ctx_tokens", "hyb_attn_pairs",
             "hyb_state_seqs")


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
    if ctx.peaks is None or "gdn_conv_channels" not in shapes:
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
        if args["what"] == "hbm":
            need += costs_hybrid.decode_tick_bytes(
                shapes, int(a["hyb_ctx_tokens"]), int(a["hyb_state_seqs"]))
        else:
            need += costs_hybrid.tick_flops(
                shapes, int(a["hyb_tokens"]), int(a["hyb_state_seqs"]),
                int(a["hyb_attn_pairs"]) + int(a["hyb_ctx_tokens"]))
        secs += x["busy"] / 1e9
        n += 1
    if not n:
        return None
    peak = ctx.peaks["hbm_bytes_per_s" if args["what"] == "hbm"
                     else "bf16_flops_per_s"]
    ctx.log(f"hybrid {args['kind']} ticks: {n} whole executions asked "
            f"{need / n / 1e9:.3f} G{'B' if args['what'] == 'hbm' else 'FLOP'}"
            f" each in {1e3 * secs / n:.3f} ms busy")
    return 100.0 * need / secs / peak
