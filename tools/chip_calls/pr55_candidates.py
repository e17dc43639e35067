"""PR 55, one-off for the chip: which form of the chunked delta rule's kernel is fastest at the Qwen3-Next
cell's shape (1,024 rows, 32 value heads, tile 128, 128 x 128 states, 33 slots)?

``tools/kernel_selftest.gdn_chunk_cell_case`` (six pools, one a layer, donated; microseconds a call of
``_gdn_chunk_call`` by the host's clock, the XLA prework of a call included, beside the recurrence's least
time) with the kernel body in these forms.  Every form keeps float32 operands at ``Precision.HIGHEST``, chunks
of 64 and the committed call's operands and scratch; only the ORDER and the SHAPE of the products differ:

* ``parent``: the kernel before PR 55 (a rolled loop over the heads of a grid step, every product of a
  chunk-head one after another, the inverse by six levels of two full 64 x 64 products), read from a copy of
  the parent commit under ``build/parent`` if there is one;
* ``null``: a body that copies ``v`` to ``o`` and carries the state: the call's prework and traffic alone;
* ``committed``: ``gdn_chunk`` as the module has it (``bb-late-ssa`` in the knobs below; ``bb-late-ssa-m16`` in
  call 3's names);
* no knob, or ``split``: phase A for all chunk-heads of the step as unrolled 2-D bodies, then phase B chunk by
  chunk and head by head, phase A's results through VMEM scratch;
* knobs, joined by ``-``: ``hb2 / hb4 / hb8`` heads a grid step; ``fused`` (a head's chunks A, B, A, B in
  order, all heads unrolled in one block) or ``rolled`` (the same inside ``fori_loop`` over the heads) in
  place of ``split``; ``full`` (the old inverse) in place of the live one; ``apart`` (no product merged:
  ``k beta k^T`` and ``q k^T``, ``T v beta`` and ``T k beta exp G``, ... each its own) in place of merged;
  ``batched`` (phase A as 3-D ``dot_general`` over the step's chunk-heads) in place of the unrolled 2-D
  bodies; ``bb`` (``batched``, and phase B as 3-D products over the heads of a chunk); ``late`` (phase A keeps
  the inverse and phase B applies it, ``T (v beta - (k beta exp G) S)``: one 64-row product fewer a chunk-head,
  one more on the serial chain); ``ssa`` (phase A's results stay values: no scratch) in place of the scratch
  refs; ``oddrows`` (the 16 -> 32 merge on the odd blocks' rows, two products of 32 rows, as every form of
  calls 1 and 2 had it; call 3's ``m16`` is the committed inverse, which makes that merge side by side).

Prints one JSON line a form.  ``--rehearse`` runs every named form once at a tiny shape in interpret mode
against ``gdn_chunk_reference`` (no chip); ``--aot`` compiles every named form for a described v5e at the
cell's shape and prints the seconds (no chip).  Nothing here is imported by the program.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from deepspeed_tpu.ops import gated_delta_rule as gdr  # noqa: E402

F32 = jnp.float32
_mm = gdr._mm


def _oddrows_inverse(a):
    """``_tri_inverse_live`` as calls 1-3 timed it before the 16 -> 32 merge joined the side-by-side form: from
    16 rows up every merge on the odd blocks' rows, ``(T_odd m) T`` (two products of 32 rows a level)."""
    c = a.shape[-1]
    d = min(gdr._DIAG, c)
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    def a21(b):
        low = ((i // (2 * b)) == (j // (2 * b))) & ((i // b) % 2 == 1) & ((j // b) % 2 == 0)
        return jnp.where(low, a, 0.0)

    diag = (i // d) == (j // d)
    full = lambda s: jnp.where(diag, jnp.concatenate([s] * (c // d), axis=-2), 0.0)
    t = (i == j).astype(F32) - a21(1)
    s = sum(t[..., u:u + d, :] for u in range(0, c, d))
    b = 2
    while b < d:
        s = s - _mm(_mm(s, a21(b)), full(s))
        b *= 2
    t = full(s)
    while b < c:
        odd = [t[..., u:u + b, :] for u in range(b, c, 2 * b)]
        y = _mm(_mm(jnp.concatenate(odd, axis=-2), a21(b)), t)
        rows = []
        for n, u in enumerate(range(0, c, 2 * b)):
            rows += [t[..., u:u + b, :], odd[n] - y[..., n * b:(n + 1) * b, :]]
        t = jnp.concatenate(rows, axis=-2)
        b *= 2
    return t


def _inverse(a, knobs):
    if "full" in knobs:
        return gdr._tri_inverse(a)
    if "oddrows" in knobs:
        return _oddrows_inverse(a)
    return gdr._tri_inverse_live(a)


def _phase_a(qc, kc, vc, beta, eg, decay, lower, knobs):
    """(T [v beta | k beta exp G], intra-chunk attention) of one chunk-head, or of a batch of them; with
    ``late`` the inverse itself in place of its product."""
    chunk = qc.shape[-2]
    kb = kc * beta
    if "apart" in knobs:
        kk, attn = _mm(kb, kc, rhs=-1), _mm(qc, kc, rhs=-1) * decay
    else:
        qk = _mm(jnp.concatenate([kb, qc], axis=-2), kc, rhs=-1)
        kk, attn = qk[..., :chunk, :], qk[..., chunk:, :] * decay
    tm = _inverse(jnp.where(lower, kk * decay, 0.0), knobs)
    if "late" in knobs:
        return tm, attn
    if "apart" in knobs:
        w = jnp.concatenate([_mm(tm, vc * beta), _mm(tm, kb * eg)], axis=-1)
    else:
        w = _mm(tm, jnp.concatenate([vc * beta, kb * eg], axis=-1))
    return w, attn


def _phase_b(st, w, attn, qc, kc, vc, beta, eg, kd, last8, knobs):
    """(o, new state) of one chunk-head, or of the heads of one chunk, from phase A's results and the state."""
    chunk, dk, dv = qc.shape[-2], st.shape[-2], st.shape[-1]
    if "late" in knobs:         # w is the inverse: T (v beta - (k beta exp G) S), one product fewer
        u = _mm(jnp.concatenate([kc * beta * eg, qc * eg], axis=-2), st)
        v_new = _mm(w, vc * beta - u[..., :chunk, :])
        o = u[..., chunk:, :] + _mm(attn, v_new)
    elif "apart" in knobs:
        v_new = w[..., :dv] - _mm(w[..., dv:], st)
        o = _mm(qc * eg, st) + _mm(attn, v_new)
    else:
        u = _mm(jnp.concatenate([w[..., dv:], qc * eg], axis=-2), st)
        v_new = w[..., :dv] - u[..., :chunk, :]
        o = u[..., chunk:, :] + _mm(attn, v_new)
    last = jnp.concatenate([jnp.broadcast_to(last8, last8.shape[:-1] + (dv,))] * (dk // 8), axis=-2)
    return o, st * last + _mm(kc * kd, v_new, lhs=-2)


def _candidate_kernel(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref, s_in_ref, o_ref, s_out_ref,
                      *, hb, tile, chunk, knobs):
    t = pl.program_id(1)
    first = jnp.logical_or(t == 0, slot_ref[jnp.maximum(t - 1, 0)] != slot_ref[t])

    @pl.when(first)
    def _():
        keep = jnp.where(reset_ref[t] != 0, 0.0, 1.0).astype(F32)
        s_out_ref[...] = s_in_ref[...] * keep

    if "ssa" in knobs or knobs & {"null", "rolled", "fused"}:
        return _candidate_body(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref, s_in_ref, o_ref,
                               s_out_ref, None, None, hb=hb, tile=tile, chunk=chunk, knobs=knobs)
    held, width = hb * (tile // chunk), v_ref.shape[-1] + k_ref.shape[-1]
    pl.run_scoped(
        lambda w_ref, a_ref: _candidate_body(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref, s_in_ref,
                                             o_ref, s_out_ref, w_ref, a_ref, hb=hb, tile=tile, chunk=chunk,
                                             knobs=knobs),
        pltpu.VMEM((held, chunk, max(width, chunk)), F32), pltpu.VMEM((held, chunk, chunk), F32))


def _candidate_body(slot_ref, reset_ref, q_ref, k_ref, v_ref, c_ref, d_ref, s_in_ref, o_ref, s_out_ref,
                    w_ref, a_ref, *, hb, tile, chunk, knobs):
    if "null" in knobs:
        o_ref[...] = v_ref[...] + q_ref[...] * k_ref[...] + c_ref[:, :, 0:1] + d_ref[:, :, 0:1]
        s_out_ref[...] = s_out_ref[...] * 0.5
        return

    per = tile // chunk
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = i > j

    def loads(hd, c):           # hd an index, or a slice of heads
        rows = pl.ds(c * chunk, chunk)
        cols = c_ref[hd, rows, :]
        return (q_ref[hd, rows, :], k_ref[hd, rows, :], v_ref[hd, rows, :], cols[..., 0:1], cols[..., 1:2],
                cols[..., 2:3], cols[..., 0:8, 3:4], d_ref[hd, rows, :])

    def put(n, w, attn):
        if "late" in knobs:
            w_ref[n, :, 0:chunk] = w
        else:
            w_ref[n] = w
        a_ref[n] = attn

    def get(n):
        return (w_ref[n, :, 0:chunk] if "late" in knobs else w_ref[n]), a_ref[n]

    def chain(hd):          # one head's chunks in order, A then B each
        st = s_out_ref[0, hd]
        for c in range(per):
            qc, kc, vc, beta, eg, kd, last8, decay = loads(hd, c)
            w, attn = _phase_a(qc, kc, vc, beta, eg, decay, lower, knobs)
            o, st = _phase_b(st, w, attn, qc, kc, vc, beta, eg, kd, last8, knobs)
            o_ref[hd, pl.ds(c * chunk, chunk), :] = o
        s_out_ref[0, hd] = st

    if "rolled" in knobs:
        jax.lax.fori_loop(0, hb, lambda hd, carry: (chain(hd), carry)[1], 0)
        return
    if "fused" in knobs:
        for hd in range(hb):
            chain(hd)
        return

    heads = slice(None)
    held = {}
    if "batched" in knobs or "bb" in knobs:
        # chunk-major: the heads of chunk c are [c * hb, (c + 1) * hb)
        parts = [loads(heads, c) for c in range(per)]
        qc, kc, vc, beta, eg, kd, last8, decay = (jnp.concatenate(x, axis=0) for x in zip(*parts))
        w, attn = _phase_a(qc, kc, vc, beta, eg, decay, lower, knobs)
        if "ssa" in knobs:
            held = {n: (w[n], attn[n]) for n in range(hb * per)}
            heldb = {c: (w[c * hb:(c + 1) * hb], attn[c * hb:(c + 1) * hb]) for c in range(per)}
        else:
            put(slice(None), w, attn)
    else:
        for hd in range(hb):
            for c in range(per):
                qc, kc, vc, beta, eg, kd, last8, decay = loads(hd, c)
                w, attn = _phase_a(qc, kc, vc, beta, eg, decay, lower, knobs)
                if "ssa" in knobs:
                    held[c * hb + hd] = (w, attn)
                else:
                    put(c * hb + hd, w, attn)
    for c in range(per):
        if "bb" in knobs:       # the heads of a chunk as one batch
            qc, kc, vc, beta, eg, kd, last8, decay = loads(heads, c)
            w, attn = heldb[c] if held else get(pl.ds(c * hb, hb))
            o, st = _phase_b(s_out_ref[0], w, attn, qc, kc, vc, beta, eg, kd, last8, knobs)
            o_ref[:, pl.ds(c * chunk, chunk), :] = o
            s_out_ref[0] = st
            continue
        for hd in range(hb):
            n = c * hb + hd
            qc, kc, vc, beta, eg, kd, last8, decay = loads(hd, c)
            w, attn = held[n] if held else get(n)
            o, st = _phase_b(s_out_ref[0, hd], w, attn, qc, kc, vc, beta, eg, kd, last8, knobs)
            o_ref[hd, pl.ds(c * chunk, chunk), :] = o
            s_out_ref[0, hd] = st


def _parent_module():
    path = os.path.join(_ROOT, "build", "parent", "deepspeed_tpu", "ops", "gated_delta_rule.py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location("gdr_parent", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["gdr_parent"] = mod
    spec.loader.exec_module(mod)
    return mod


def form(name: str):
    """``call(pool, q, k, v, g, beta, tile_slot, tile_reset, tile, interpret)`` of one named form."""
    knobs = set(name.split("-"))
    hb = next((int(k[2:]) for k in knobs if k.startswith("hb") and k[2:].isdigit()), None)

    def call(pool, q, k, v, g, beta, tile_slot, tile_reset, tile, interpret=False):
        h = q.shape[1]
        chunk = min(gdr.CHUNK, tile)
        if "parent" in knobs:
            mod = _parent_module()
            return mod._gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset, tile, chunk,
                                       mod._head_block(h, hb or 4), interpret)
        if knobs == {"committed"}:               # the committed entry itself
            return gdr.gdn_chunk(pool, q, k, v, g, beta, tile_slot, tile_reset, tile, interpret=interpret)
        if knobs == {"committed", f"hb{hb}"}:    # the committed body at another head block
            return gdr._gdn_chunk_call(pool, q, k, v, g, beta, tile_slot, tile_reset, tile, chunk,
                                       gdr._head_block(h, hb), interpret)
        kernel = functools.partial(_candidate_kernel, knobs=knobs)
        saved, gdr._gdn_chunk_kernel = gdr._gdn_chunk_kernel, _named(kernel)
        try:
            return gdr._gdn_chunk_call.__wrapped__(pool, q, k, v, g, beta, tile_slot, tile_reset, tile,
                                                   chunk, gdr._head_block(h, hb or 4), interpret)
        finally:
            gdr._gdn_chunk_kernel = saved
    return call


def _named(kernel):
    def _gdn_chunk_kernel(*refs, **kw):
        return kernel(*refs, **kw)
    return _gdn_chunk_kernel


def _rehearse(names):
    import numpy as np

    rng = np.random.default_rng(0)
    unit = lambda y: y / np.sqrt((y * y).sum(-1, keepdims=True))
    f = lambda a: jnp.asarray(a, F32)
    tile, rows, h, dk, dv = 128, 512, 8, 32, 48
    ops = (f(rng.standard_normal((5, h, dk, dv))), f(unit(rng.standard_normal((rows, h, dk))) * dk ** -0.5),
           f(unit(rng.standard_normal((rows, h, dk)) + 0.5)), f(rng.standard_normal((rows, h, dv))),
           f(-0.05 * np.abs(rng.standard_normal((rows, h)))),
           f(1 / (1 + np.exp(-rng.standard_normal((rows, h))))),
           jnp.asarray([2, 2, 0, 4], jnp.int32), jnp.asarray([1, 0, 0, 0], bool))
    want = gdr.gdn_chunk_reference(*ops, tile)
    for name in names:
        if "null" in name or ("parent" in name and _parent_module() is None):
            continue
        got = form(name)(*ops, tile, interpret=True)
        print(json.dumps({"form": name, "rehearsed": True, "max_diff": [
            float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))) for a, b in zip(got, want)]}), flush=True)


def _aot(names):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dt=F32: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    rows, h, d, tile = 1024, 32, 128, 128
    args = (s((33, h, d, d)), s((rows, h, d)), s((rows, h, d)), s((rows, h, d)), s((rows, h)), s((rows, h)),
            s((rows // tile,), jnp.int32), s((rows // tile,), jnp.bool_))
    for name in names:
        if "parent" in name and _parent_module() is None:
            continue
        t0 = time.time()
        try:
            jax.jit(lambda *a, c=form(name): c(*a, tile)).lower(*args).compile()
            print(json.dumps({"form": name, "aot_s": round(time.time() - t0, 1)}), flush=True)
        except Exception as e:     # noqa: BLE001 - what the chip's compiler refuses is the finding
            print(json.dumps({"form": name, "aot_error": str(e)[-600:]}), flush=True)


def main(argv):
    flags = [a for a in argv if a.startswith("--")]
    names = [a for a in argv if not a.startswith("--")]
    if "--rehearse" in flags:
        return _rehearse(names)
    if "--aot" in flags:
        return _aot(names)
    import kernel_selftest as ks

    from deepspeed_tpu.utils.platform import require_tpu

    require_tpu("pr55_candidates")
    for name in names:
        if "parent" in name and _parent_module() is None:
            print(json.dumps({"form": name, "error": "no build/parent"}), flush=True)
            continue
        try:
            out = ks.gdn_chunk_cell_case(call=form(name), check="null" not in name)
        except Exception as e:     # noqa: BLE001
            out = {"error": str(e)[-600:]}
        print(json.dumps({"form": name, **out}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
