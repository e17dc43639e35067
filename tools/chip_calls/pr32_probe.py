"""PR 32, one-off for the chip: what does a work unit that holds no rows cost?

``tools/kernel_selftest.gmm_share_case`` (the three share shapes of the serving cells, seeded
routing, microseconds a call) with the forward call of ``ops/grouped_gemm.py`` in three forms:

* ``parent``: the kernel body as it was before PR 32 (every unit multiplies and stores);
* ``guarded``: the committed one (a unit with an empty row range runs no dot and no store);
* ``dynamic``: the committed body under a DYNAMIC bound on the work-unit axis (``num_work``), so
  that the skipped steps are not run at all: the difference to ``guarded`` is what the skipped
  steps themselves cost, which decides whether the kernel gets such a bound (ISSUE 32).

Prints one JSON line a form and pass; nothing here is imported by the program.
"""

from __future__ import annotations

import functools
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tools"))


def _parent_body(group_ids, m_tile_ids, row_start, row_end, lhs_ref, rhs_ref, out_ref, *, tile_m):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w = pl.program_id(1)
    mt = m_tile_ids[w]
    rows = mt * tile_m + jax.lax.broadcasted_iota(jnp.int32, (tile_m, 1), 0)
    keep = (rows >= row_start[w]) & (rows < row_end[w])

    @pl.when(jnp.logical_or(w == 0, m_tile_ids[w - 1] != mt))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    partial = jax.lax.dot_general(lhs_ref[:], rhs_ref[0], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    out_ref[:] = jnp.where(keep, partial.astype(out_ref.dtype), out_ref[:])


def _forward(body, dynamic: bool):
    """``_gmm_fwd_kernel_call`` with ``body`` as the kernel and, if ``dynamic``, ``num_work`` as
    the bound of the work-unit axis."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deepspeed_tpu.ops import grouped_gemm as gg

    @functools.partial(jax.jit, static_argnames=("tile_m", "tile_n", "interpret"))
    def call(lhs, rhs, group_sizes, tile_m, tile_n, interpret):
        m, k = lhs.shape
        n = rhs.shape[2]
        gids, mtids, rs, re_, nw = gg.make_group_metadata(group_sizes, m, tile_m)
        units = nw.astype(jnp.int32) if dynamic else gids.shape[0]
        out = pl.pallas_call(
            functools.partial(body, tile_m=tile_m),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(n // tile_n, units),
                in_specs=[pl.BlockSpec((tile_m, k), lambda j, w, g, mt, rs, re: (mt[w], 0)),
                          pl.BlockSpec((1, k, tile_n), lambda j, w, g, mt, rs, re: (g[w], 0, j))],
                out_specs=pl.BlockSpec((tile_m, tile_n), lambda j, w, g, mt, rs, re: (mt[w], j))),
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype), interpret=interpret,
        )(gids, mtids, rs, re_, lhs, rhs)
        total = jnp.sum(group_sizes)
        return jnp.where(jnp.arange(m, dtype=jnp.int32)[:, None] < total, out, 0)
    return call


def main():
    import kernel_selftest as k

    from deepspeed_tpu.ops import grouped_gemm as gg
    from deepspeed_tpu.utils.platform import require_tpu

    require_tpu("pr32_probe.py")
    committed = gg._gmm_fwd_kernel_call
    forms = {"parent": _forward(_parent_body, False), "guarded": committed,
             "dynamic": _forward(gg._gmm_kernel, True)}
    # call 1 ran all six passes (and timed a 0.65 ms copy of each layer's weights beside every call: the
    # self-test case then sliced one stacked argument); a later call names its passes as arguments
    for form in sys.argv[1:] or ("parent", "guarded", "dynamic", "dynamic", "guarded", "parent"):
        gg._gmm_fwd_kernel_call = forms[form]
        try:
            print(json.dumps({"form": form, **k.gmm_share_case(3e-2)}), flush=True)
        finally:
            gg._gmm_fwd_kernel_call = committed


if __name__ == "__main__":
    main()
