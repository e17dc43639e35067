"""Device milliseconds per step, or per pure-decode tick, of the operations
whose ``op_name`` lies under a ``jax.named_scope`` (or flax module scope) of
the program: ``attn/dense_read``, ``mlp``, ``lm_head_loss``, ``optimizer/``.

``TraceView`` keeps an event's instruction text only, and the ``op_name`` is
not in it.  A TPU profile keeps it as the ``tf_op`` statistic of the
instruction's event-metadata record, which ``jax.profiler.ProfileData``
does not hand out, so this reader has ``lib/xplane_ops.py`` read the file's
wire format (first chip call of PR 23: 98% of the device time of both kinds
of cell lies in operations that carry it; asynchronous copy / slice ``-done``
operations do not).  A fusion carries the ``op_name`` of its root
instruction.  ``per: step`` divides the traced stretch by its steps;
``per: decode_tick`` keeps the part of each operation inside the pure-decode
ticks of the stretch (``bench/tick`` spans that hold an
``engine/decode_step``, as ``decode_hbm_pct`` finds them) and divides by
their number, so the mixed ticks the stretch caught do not move it.
``exclude`` (optional) drops events whose label matches (Mosaic calls and
collectives, which have metrics of their own).  Logs once a run the device
time under every scope, two levels deep.  None, and a logged line that
says so, when no operation carries an ``op_name`` under the scope (a
program from before PR 23 has no such scopes; nor has an executable that
the persistent compile cache kept from such a tree, since its default key
ignores scopes: ``deepspeed_tpu/utils/compile_cache.py``).  args: scope (a
regular expression searched in the ``op_name``), per (step|decode_tick)[,
exclude]."""

import bisect
import re

from benchmark.lib import tracing, xplane_ops

_WRAPPER = re.compile(r"^(jit|jvp|transpose|pjit|checkpoint|remat)\(|"
                      r"^(layers|h)_\d+$|^(model|shard_map)$")


def scope_key(op_name: str) -> str:
    """The first two scopes of an ``op_name`` once the jit / autodiff
    wrappers, the layer index and the primitive's own name are dropped:
    ``jit(run)/layers_3/attn/dense_read/dot_general`` -> ``attn/dense_read``."""
    parts = [p for p in op_name.split("/")[:-1] if not _WRAPPER.search(p)]
    return "/".join(parts[:2]) or "(no scope)"


def _events(facts):
    """[(device, start, end, op_name, label)] of the trace's operations
    that carry an ``op_name``, read once a run."""
    if "_scope_events" not in facts:
        path = (facts.get("capture") or {}).get("xplane")
        facts["_scope_events"] = [
            (dev, s, e, op.rstrip(":"), tracing.label_of(text))
            for dev, s, e, op, text in
            (xplane_ops.device_ops(path) if path else ()) if op]
    return facts["_scope_events"]


def _decode_ticks(view):
    """[(start, end)] of the stretch's pure-decode ticks."""
    starts = [e.start for e in view.host_named(r"^engine/decode_step$")]
    out = []
    for t in view.host_named(r"^bench/tick$"):
        i = bisect.bisect_left(starts, t.start)
        if i < len(starts) and starts[i] < t.end:
            out.append((t.start, t.end))
    return out


def read(facts, args, ctx):
    view = facts.get("view")
    if view is None or not view.devices:
        return None
    events = _events(facts)
    if not events:
        return None
    if args["per"] == "step":
        n, inside = facts.get("traced_steps"), None
    else:
        inside = tracing.union(_decode_ticks(view))
        n = len(inside)
    if not n:
        return None
    nd = len(view.devices)

    def device_ns(picked):
        """Nanoseconds of the picked (device, start, end) operations,
        inside the pure-decode ticks if those are asked for, averaged over
        the devices."""
        per_dev = {}
        for dev, s, e in picked:
            per_dev.setdefault(dev, []).append((s, e))
        total = 0
        for spans in per_dev.values():
            spans = tracing.union(spans)
            if inside is not None:
                spans = tracing.subtract(spans, tracing.gaps(
                    inside, spans[0][0], spans[-1][1]))
            total += tracing.total(spans)
        return total / nd

    if "_scope_logged" not in facts:
        facts["_scope_logged"] = True
        by_key = {}
        for dev, s, e, op, _label in events:
            by_key.setdefault(scope_key(op), []).append((dev, s, e))
        rows = sorted(((device_ns(v), k) for k, v in by_key.items()),
                      reverse=True)
        every = device_ns([ev[:3] for ev in events])
        busy = device_ns([(e.device, e.start, e.end)
                          for e in view.device_events])
        ctx.log(f"device ms per {args['per']} by scope ({n} of them; busy "
                f"{busy / n / 1e6:.3f}, in operations with an op_name "
                f"{every / n / 1e6:.3f}): " + ", ".join(
                    f"{k} {v / n / 1e6:.3f}" for v, k in rows[:16]))
    rx = re.compile(args["scope"])
    ex = re.compile(args["exclude"]) if args.get("exclude") else None
    ns = device_ns([(dev, s, e) for dev, s, e, op, label in events
                    if rx.search(op) and not (ex and ex.search(label))])
    if not ns:
        # said aloud: a metric that is merely left out reads like a cell
        # that never had it
        ctx.log(f"no operation of the trace has an op_name under "
                f"{args['scope']!r}: the program that ran has no such "
                f"scope (a tree from before it, or an executable that the "
                f"compile cache kept from one)")
        return None
    return ns / n / 1e6
