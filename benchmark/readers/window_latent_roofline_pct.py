"""Share of its roofline a read of window layers with latent rows reaches:
the least time the chip could take for what the traced stretch's forwards
asked of it (the larger of FLOPs over the peak and bytes over the HBM peak,
``lib/costs_window_latent.py``: what the mathematics needs, not what the
path executes), summed over those forwards, over the read's device time in
the stretch.  Both sums are over the stretch, as ``banded_roofline_pct``
does.

What was asked comes from the program's own counters, moved onto the
profiler's clock, on the ``engine/build_batch`` and ``engine/decode_prep``
spans that start inside the stretch.  ``which: walk`` (the one-token rows):
``read_keys_win``, the keys inside the band summed over rows and window
layers.  ``which: prefill`` (the chunks): ``attn_pairs_win`` (banded pairs)
and ``ctx_rows_win`` (band rows), a layer.  The device time is that of the
calls of a kernel (``pattern``, its ``kernel_metadata`` name) or of the
operations under a scope (``scope``, searched in the ``op_name``), by the
accepted ``dsa_roofline_pct._device_ns``.

None when nothing of the pattern or scope is in the trace, when the program
records no such counter (a tree from before it: the span has
``read_blocks_win`` and no ``read_keys_win``), when the configuration has no
window layer with a latent row, or without peaks.  args: which, pattern |
scope."""

from benchmark.lib import costs, costs_window_latent
from benchmark.readers import _host_labels
from benchmark.readers.dsa_roofline_pct import _device_ns

_SPANS = ("engine/build_batch", "engine/decode_prep")
_COUNTERS = {"walk": ("read_keys_win",),
             "prefill": ("attn_pairs_win", "ctx_rows_win")}


def asked(facts, which):
    """The counters' values on the dispatches that start inside the
    stretch (on the profiler's clock) and carry every one of them."""
    off = _host_labels.offset_ns(facts)
    if off is None:
        return []
    lo, hi = facts["view"].window()
    names = _COUNTERS[which]
    out = []
    for r in _host_labels.tracer_spans(facts):
        at = r.get("attrs") or {}
        if r["name"] in _SPANS and all(n in at for n in names) \
                and lo <= r["t0_ns"] + off <= hi:
            out.append(tuple(int(at[n]) for n in names))
    return out


def read(facts, args, ctx):
    view, shapes = facts.get("view"), facts.get("shapes") or {}
    if view is None or ctx.peaks is None \
            or "window_latent_layers" not in shapes:
        return None
    which = args["which"]
    cost = costs_window_latent.walk_costs if which == "walk" \
        else costs_window_latent.chunk_costs
    work = [w for w in asked(facts, which) if any(w)]
    took = _device_ns(facts, args) / 1e9
    if not work or took <= 0:
        return None
    least = sum(costs.roofline(*cost(shapes, *w), 1.0, ctx.peaks)["least_s"]
                for w in work)
    ctx.log(f"window latent {which} roofline: {len(work)} forwards of the "
            f"stretch asked {[sum(c) for c in zip(*work)]} "
            f"({' / '.join(_COUNTERS[which])}), least "
            f"{1e3 * least / len(work):.3f} ms of "
            f"{1e3 * took / len(work):.3f} ms a forward")
    return 100.0 * least / took
