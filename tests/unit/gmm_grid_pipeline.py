"""The forward grouped GEMM as it was before PR 53: the expert weights on
``pallas_call``'s grid pipeline (a ``(1, K, tile_n)`` block spec, fetched ONE
grid step ahead), where ``ops/grouped_gemm.py::_gmm_kernel`` now fills a ring
of VMEM slots itself.  Kept as the oracle of the ring's bit-for-bit tests
(``test_grouped_gemm.py``) and as the other side of the chip's probe
(``tools/chip_calls/pr53_probe.py``); nothing in the program imports it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops import grouped_gemm as gg


def grid_pipeline_kernel(group_ids, m_tile_ids, row_start, row_end, lhs_ref,
                         rhs_ref, out_ref, *, tile_m: int):
    w = pl.program_id(1)
    mt = m_tile_ids[w]

    @pl.when(jnp.logical_or(w == 0, m_tile_ids[w - 1] != mt))
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(row_end[w] > row_start[w])
    def _():
        rows = mt * tile_m + jax.lax.broadcasted_iota(
            jnp.int32, (tile_m, 1), 0)
        keep = (rows >= row_start[w]) & (rows < row_end[w])
        partial = jax.lax.dot_general(
            lhs_ref[:], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out_ref[:] = jnp.where(keep, partial.astype(out_ref.dtype),
                               out_ref[:])


def grid_pipeline_gmm(lhs, rhs, group_sizes, tile_m: int, tile_n: int,
                      interpret: bool, kernel=grid_pipeline_kernel):
    """``_gmm_fwd_kernel_call`` of the parent, unjitted; ``kernel`` = a body
    of the parent's signature (the tests' pre-PR 32 oracle is one)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    gids, mtids, rs, re_, _ = gg.make_group_metadata(group_sizes, m, tile_m)
    need = gg._forward_vmem(tile_m, k, tile_n, lhs.dtype.itemsize)
    limit = {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + gg._VMEM_HEADROOM)} \
        if need > gg._VMEM_BUDGET else {}
    out = pl.pallas_call(
        functools.partial(kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tile_n, gids.shape[0]),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda j, w, g, mt, rs, re: (mt[w], 0)),
                pl.BlockSpec((1, k, tile_n),
                             lambda j, w, g, mt, rs, re: (g[w], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tile_n),
                lambda j, w, g, mt, rs, re: (mt[w], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        **limit,
    )(gids, mtids, rs, re_, lhs, rhs)
    total = jnp.sum(group_sizes)
    return jnp.where(jnp.arange(m, dtype=jnp.int32)[:, None] < total,
                     out, 0)
