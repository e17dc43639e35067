"""Tensor parallelism's collectives under the GEMMs beside them.

Megatron-style tensor parallelism stated as partition rules alone leaves one
all-reduce of the whole ``[B, T, H]`` activation after every row-parallel
GEMM, and its only consumer is the residual add and the norm that feed the
next GEMM: nothing independent can run beside it.  Here the collective is
decomposed into ring steps over TOKEN chunks, each sent with
``lax.ppermute`` while the next chunk is multiplied (collective matmul:
Wang et al., ASPLOS 2023, "Overlap communication with dependent computation
via decomposition"), and between a row-parallel GEMM and the next
column-parallel one the residual stream lives token-sharded over ``model``.

For ``n`` = the size of ``model``, a rank's tokens in ``n`` chunks of
``T / n``:

* :func:`row_parallel_scatter` ``x [B, T, F/n], w [F/n, H] -> [B, T/n, H]``:
  the product's all-reduce as a ring reduce-scatter;
* :func:`gather_column_parallel` ``x [B, T/n, H], ws -> [B, T, F/n]`` each:
  the all-gather of the token-sharded stream as a ring under the products;
* :func:`gated_mlp`: the two around an elementwise gate, the chunks never
  put together in between.

Each is a ``jax.shard_map`` over ``model`` ONLY: every other mesh axis
stays with GSPMD, so a batch sharded over ``data`` and ZeRO's weight
gathers / gradient reduce-scatters are placed as before.  The gather and
the scatter are each other's backward pass (the gather's is a ring scatter
of the input gradient and the scatter's a ring gather of the output's),
cut along the same chunks; a weight gradient is ONE product over the
rank's tokens put together, the parent's GEMM and its one rounding.  What
a site keeps for the backward pass is a rank's own chunk (and
``gated_mlp`` its gate and up rows): XLA's temporaries for the Mistral
cell's step are the parent's 4.30 GB.

:func:`plan` is the rule for when the ring engages, from what the code can
see; there is no option.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

AXIS = "model"
# A chunk too short costs more in permutes and small products than its
# transfer hides.  On the chip (PR 62, call 7: a Mistral-7B layer's two
# sublayers alone over data=2 x model=2, forward + backward, the ring over
# GSPMD's all-reduce): 256-row chunks 1.015 (MLP) and 0.971 (attention's
# projections), 512 rows 0.971 and 0.940, 1,024 rows 0.937 and 0.951, 2,048
# rows 0.923 and 0.930.  The smallest chunk at which both sublayers win.
MIN_CHUNK_ROWS = 512
# Pieces a hop of a scatter: XLA fuses ``+ received`` into the next
# product's output, so with one piece a hop that product waits for the very
# send it was to cover; with two, piece 1's product covers piece 0's send.
# On the chip (PR 62, the Mistral-7B sublayers alone): one piece 17.19 ms
# an MLP layer, two 16.58, four 16.64; the backward scatters alone in one
# piece cost the cell's step 5 ms (their 2,048-row products ran slower).
SCATTER_PIECES = 2
RING_SCOPE = "tp/ring"


@dataclasses.dataclass(frozen=True)
class Ring:
    """An engaged ring: the mesh and the size of its ``model`` axis."""
    mesh: Any
    steps: int

    def _spec(self, *dims) -> NamedSharding:
        return NamedSharding(self.mesh, P(*dims))

    def shard_tokens(self, x):
        """``[B, T, H]`` into the stream's placement: tokens over
        ``model``, the batch wherever GSPMD has it."""
        return lax.with_sharding_constraint(
            x, self._spec(P.UNCONSTRAINED, AXIS, None))

    def gather_tokens(self, x):
        """The stream whole in tokens again (one all-gather)."""
        return lax.with_sharding_constraint(
            x, self._spec(P.UNCONSTRAINED, None, None))


# ------------------------------------------------------------------ #
# The counter: what the rule decided while a program was traced.
# ------------------------------------------------------------------ #
_recorders = []


@contextlib.contextmanager
def recording():
    """Collects, while a model is traced inside it, how many GEMM sites
    took the ring (``ring``, of ``steps`` steps) and how many fell back
    and why (``fallbacks``: reason -> sites)."""
    sites: Dict[str, Any] = {"ring": 0, "steps": 0, "fallbacks": {}}
    _recorders.append(sites)
    try:
        yield sites
    finally:
        # by identity: two open recorders may hold equal counts
        del _recorders[next(i for i, r in enumerate(_recorders)
                            if r is sites)]


def _note_ring(steps: int) -> None:
    for sites in _recorders:
        sites["ring"] += 1
        sites["steps"] = steps


def _note_fallback(reason: str, n_sites: int) -> None:
    for sites in _recorders:
        sites["fallbacks"][reason] = \
            sites["fallbacks"].get(reason, 0) + n_sites


def describe(sites: Dict[str, Any]) -> str:
    fb = sites["fallbacks"]
    why = "".join(f" ({n}: {reason})" for reason, n in sorted(fb.items()))
    return (f"tp_overlap: {sites['ring']} ring sites of {sites['steps']} "
            f"steps, {sum(fb.values())} fallbacks{why}")


# ------------------------------------------------------------------ #
# The rule
# ------------------------------------------------------------------ #
def plan(tokens: int, *, sites: int, cache=None,
         features: Sequence[int] = ()) -> Optional[Ring]:
    """The ring for a whole-sequence forward of ``tokens`` positions, or
    None: no topology or a ``model`` axis of 1 (not tensor parallel: not
    counted), a caller already inside a manual region, a KV ``cache``,
    tokens that do not divide by the axis, a chunk under
    ``MIN_CHUNK_ROWS`` rows, a mesh that also splits the sequence, or a
    sharded width in ``features`` that does not divide.  ``sites`` is how
    many GEMM sites the decision stands for, for the counter."""
    from deepspeed_tpu.parallel import groups

    topo = groups.get_topology(optional=True)
    if topo is None:
        return None
    mesh = topo.mesh
    n = int(mesh.shape.get(AXIS, 1))
    if n <= 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        reason = "inside a manual region"
    elif cache is not None:
        reason = "a KV cache"
    elif mesh.shape.get("seq", 1) > 1:
        reason = "a seq axis"
    elif tokens % n:
        reason = f"T % {n} != 0"
    elif tokens // n < MIN_CHUNK_ROWS:
        reason = f"a chunk under {MIN_CHUNK_ROWS} rows"
    elif any(f % n for f in features):
        reason = f"a width % {n} != 0"
    else:
        return Ring(mesh, n)
    _note_fallback(reason, sites)
    return None


# ------------------------------------------------------------------ #
# The ring, on a rank's shards (inside the manual region)
# ------------------------------------------------------------------ #
def _send(x, n: int):
    with jax.named_scope(RING_SCOPE):
        return lax.ppermute(x, AXIS, [(i, (i + 1) % n) for i in range(n)])


def _ring_gather(n: int, x):
    """``[x, x one hop on, ...]``: entry ``k`` is the chunk of rank
    ``r - k``.  A send depends on the send before it alone, so the
    products of entry ``k`` run while entry ``k + 1`` travels."""
    chunks = [x]
    for _ in range(n - 1):
        chunks.append(_send(chunks[-1], n))
    return chunks


def _ring_scatter(n: int, part):
    """Sum over the ranks of ``part(k)`` (the pieces of a rank's partial
    result for the chunk of rank ``r - k``), the rank left with its own
    chunk's sum.  Step ``j`` takes the chunk that still has ``n - 1 - j``
    hops to go (rank ``r - (j + 1)``'s), adds what arrived and sends the
    sum on while step ``j + 1`` multiplies."""
    acc = None
    for j in range(n):
        ys = part((j + 1) % n)
        if acc is not None:
            ys = [y + a for y, a in zip(ys, acc)]
        acc = [_send(y, n) for y in ys] if j < n - 1 else ys
    return jnp.concatenate(acc, axis=1)


def _rows_of(n: int, rank, a, k: int):
    """The rows of rank ``r - k``'s chunk in ``a [B, T, ...]``."""
    rows = a.shape[1] // n
    return lax.dynamic_slice_in_dim(a, ((rank - k) % n) * rows, rows, axis=1)


def _placed(n: int, rank, ys):
    """``[B, T, F]`` from ``ys[k] [B, T/n, F]`` = rank ``r - k``'s rows."""
    b, rows, f = ys[0].shape
    out = jnp.zeros((b, n * rows, f), ys[0].dtype)
    for k, y in enumerate(ys):
        out = lax.dynamic_update_slice_in_dim(
            out, y, ((rank - k) % n) * rows, axis=1)
    return out


def _in_pieces(x):
    """A chunk's rows in the scatter's pieces."""
    rows = x.shape[1]
    pieces = SCATTER_PIECES if rows % (8 * SCATTER_PIECES) == 0 else 1
    return jnp.split(x, pieces, axis=1)


def _whole(chunks):
    """A rank's tokens whole, in the ring's order (entry ``k`` = rank
    ``r - k``'s rows): all that a contraction over the tokens asks."""
    return jnp.concatenate(chunks, axis=1)


def _dw(x, dy):
    """``x [B, T, F]^T dy [B, T, H]`` -> ``[F, H]``: a weight gradient,
    ONE product over all of a rank's tokens as under the partition rules
    alone, so it is accumulated and rounded as there (and reduced over
    ``data`` in the same dtype).  The sum of per-chunk products rounds
    once more a chunk in bf16, and as float32 terms it takes the
    reduction over ``data`` to float32 with it."""
    return jnp.einsum("btf,bth->fh", x, dy)


# The two directions are each other's backward pass: the gather's is a
# ring scatter of ``dy w^T`` and the scatter's a ring gather of ``dy``.  A
# weight gradient is one product over the rank's tokens put together (the
# parent's GEMM and its one rounding), the input gradients go chunk by
# chunk.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gather_products(n, names, rank, x, ws):
    return _gather_products_fwd(n, names, rank, x, ws)[0]


def _gather_products_fwd(n, names, rank, x, ws):
    chunks = _ring_gather(n, x)
    outs = []
    for name, w in zip(names, ws):
        with jax.named_scope(name):
            outs.append(_placed(n, rank, [jnp.dot(c, w) for c in chunks]))
    return tuple(outs), (rank, x, ws)


def _gather_products_bwd(n, names, res, dys):
    rank, x, ws = res
    # gathered once more rather than kept (XLA shares the forward's sends
    # where it keeps their results): a site's residual is a rank's chunk
    x_all = _placed(n, rank, _ring_gather(n, x))
    dws, dxs = [], [None] * n            # dxs[k]: chunk k's dx, in pieces
    for name, w, dy in zip(names, ws, dys):
        with jax.named_scope(name):
            dws.append(_dw(x_all, dy))
            for k in range(n):
                ds = [jnp.dot(p, w.T)
                      for p in _in_pieces(_rows_of(n, rank, dy, k))]
                dxs[k] = ds if dxs[k] is None else \
                    [a + d for a, d in zip(dxs[k], ds)]
    return None, _ring_scatter(n, lambda k: dxs[k]), tuple(dws)


_gather_products.defvjp(_gather_products_fwd, _gather_products_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scatter_product(n, name, rank, x, w):
    return _scatter_product_fwd(n, name, rank, x, w)[0]


def _scatter_product_fwd(n, name, rank, x, w):
    def part(k):
        with jax.named_scope(name):
            return [jnp.dot(p, w)
                    for p in _in_pieces(_rows_of(n, rank, x, k))]

    return _ring_scatter(n, part), (rank, x, w)


def _scatter_product_bwd(n, name, res, dy):
    rank, x, w = res
    dys = _ring_gather(n, dy)
    with jax.named_scope(name):
        dx = _placed(n, rank, [jnp.dot(d, w.T) for d in dys])
        dw = _dw(x, _placed(n, rank, dys))
    return None, dx, dw


_scatter_product.defvjp(_scatter_product_fwd, _scatter_product_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _gated_mlp(n, act, names, x, wg, wu, wd):
    return _gated_mlp_fwd(n, act, names, x, wg, wu, wd)[0]


def _gated_mlp_fwd(n, act, names, x, wg, wu, wd):
    gate, up, down = names
    gs, us = [], []
    for c in _ring_gather(n, x):
        with jax.named_scope(gate):
            gs.append(jnp.dot(c, wg))
        with jax.named_scope(up):
            us.append(jnp.dot(c, wu))

    def part(k):
        with jax.named_scope(down):
            return [jnp.dot(p, wd) for p in _in_pieces(act(gs[k]) * us[k])]

    return _ring_scatter(n, part), (x, gs, us, wg, wu, wd)


def _gated_mlp_bwd(n, act, names, res, dy):
    gate, up, down = names
    x, gs, us, wg, wu, wd = res
    xs, dys = _ring_gather(n, x), _ring_gather(n, dy)
    hs, dgs, dus = [], [], []
    for g, u, d in zip(gs, us, dys):
        h, back = jax.vjp(lambda g, u: act(g) * u, g, u)
        with jax.named_scope(down):
            dg, du = back(jnp.dot(d, wd.T))
        hs.append(h)
        dgs.append(dg)
        dus.append(du)
    x_all = _whole(xs)
    with jax.named_scope(down):
        dwd = _dw(_whole(hs), _whole(dys))
    with jax.named_scope(gate):
        dwg = _dw(x_all, _whole(dgs))
    with jax.named_scope(up):
        dwu = _dw(x_all, _whole(dus))

    def part(k):
        ds = []
        for dg, du in zip(_in_pieces(dgs[k]), _in_pieces(dus[k])):
            with jax.named_scope(gate):
                d = jnp.dot(dg, wg.T)
            with jax.named_scope(up):
                ds.append(d + jnp.dot(du, wu.T))
        return ds

    return _ring_scatter(n, part), dwg, dwu, dwd


_gated_mlp.defvjp(_gated_mlp_fwd, _gated_mlp_bwd)


# ------------------------------------------------------------------ #
# The helpers
# ------------------------------------------------------------------ #
def _ranks(n: int):
    """A rank's index along ``model`` as the one element it holds of
    ``arange(n)`` sharded over the axis: ``lax.axis_index`` lowers to a
    PartitionId, which the TPU's SPMD partitioner refuses while other mesh
    axes are left to it."""
    return jnp.arange(n, dtype=jnp.int32)


# Each is jitted on its ring and names, so that a model traces a site's
# body once a signature and not once a layer (24 sites, three signatures
# in the Mistral cell: set-up is an end-to-end metric).
@functools.partial(jax.jit, static_argnums=(0, 1))
def _scatter_site(ring: Ring, name: str, x, w):
    n = ring.steps
    return jax.shard_map(
        lambda rank, x, w: _scatter_product(n, name, rank[0], x, w),
        mesh=ring.mesh, axis_names={AXIS},
        in_specs=(P(AXIS), P(None, None, AXIS), P(AXIS, None)),
        out_specs=P(None, AXIS, None), check_vma=False)(_ranks(n), x, w)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gather_site(ring: Ring, names, x, *ws):
    n = ring.steps
    return jax.shard_map(
        lambda rank, x, *ws: _gather_products(n, names, rank[0], x, ws),
        mesh=ring.mesh, axis_names={AXIS},
        in_specs=(P(AXIS), P(None, AXIS, None)) +
        (P(None, AXIS),) * len(names),
        out_specs=(P(None, None, AXIS),) * len(names),
        check_vma=False)(_ranks(n), x, *ws)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _gated_mlp_site(ring: Ring, act, names, x, wg, wu, wd):
    n = ring.steps
    col, row = P(None, AXIS), P(AXIS, None)
    return jax.shard_map(
        lambda x, wg, wu, wd: _gated_mlp(n, act, names, x, wg, wu, wd),
        mesh=ring.mesh, axis_names={AXIS},
        in_specs=(P(None, AXIS, None), col, col, row),
        out_specs=P(None, AXIS, None), check_vma=False)(x, wg, wu, wd)


def row_parallel_scatter(ring: Ring, x, w, *, name: str):
    """``x [B, T, F] @ w [F, H]`` with ``F`` sharded over ``model``,
    reduced over ``model`` and left token-sharded: ``[B, T, H]`` placed
    ``P(., 'model', None)``.  A rank multiplies a chunk's rows in at least
    two pieces, adds the partial sum that arrived and sends the sum on
    while the next piece is multiplied; it ends with its own chunk.  The
    products carry the scope ``name``, the permutes ``tp/ring``."""
    _note_ring(ring.steps)
    return _scatter_site(ring, name, x, w)


def gather_column_parallel(ring: Ring, x, ws: Dict[str, Any]):
    """``x [B, T, H]`` token-sharded over ``model`` times every weight of
    ``ws`` (name -> ``[H, F]``, ``F`` sharded over ``model``): a dict of
    ``[B, T, F]`` whole in tokens, placed ``P(., None, 'model')``.  A rank
    multiplies the chunk it holds by every weight, under the scope of the
    weight's name, and writes the rows at the chunk's place while
    ``ppermute`` brings the next chunk."""
    _note_ring(ring.steps)
    names = tuple(ws)
    return dict(zip(names, _gather_site(ring, names, x, *ws.values())))


def gated_mlp(ring: Ring, x, w_gate, w_up, w_down, act,
              names=("gate_proj", "up_proj", "down_proj")):
    """``(act(x @ w_gate) * (x @ w_up)) @ w_down``, ``x`` and the result
    token-sharded over ``model``: the gather and the scatter of one
    sublayer in one manual region, two GEMM sites.  A chunk's gate and up
    rows go straight into its piece of the down projection, so nothing of
    the ``[B, T, F]`` intermediates is put together (two 58 MB copies a
    layer at Mistral-7B's widths) or kept besides gate and up; a weight
    gradient's GEMM takes the chunks as they are."""
    _note_ring(ring.steps)
    _note_ring(ring.steps)
    return _gated_mlp_site(ring, act, tuple(names), x, w_gate, w_up, w_down)
