"""PR 52, call 2 (1 chip): the three latent kernels at 64 heads against
plain compositions, compiled on the chip at the cell's shapes (they have
run at Moonlight's 16 heads only; call 1's check read 0.67 with every layer
right, which the CPU tests at float32 and in interpret mode do not).

* the absorbed decode walk: 64 rows of 64 heads over a 1,024-block pool
  behind tables 32 wide, against ``absorbed_read_xla``;
* expand + the expanded prefill read: a chunk of ``rows`` tokens of one
  sequence from ``start``, against a per-head composition of the chunk (its
  context expanded once, float32 softmax; what ``tools/kernel_selftest.py::
  latent_prefill_cell_case`` does at 16 heads), at unit queries and at
  queries of the spread the first seeding gave (scores of std ~17).

    python3 benchmark/tools/calls/pr52_call02_kernels.py
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from deepspeed_tpu.inference.v2.kernels import latent_flash as lf  # noqa: E402
from deepspeed_tpu.inference.v2.model_implementations. \
    ragged_deepseek_v3 import absorbed_read_xla         # noqa: E402

BS, TILE = 128, 128
RANK, NOPE, ROPE, VD, W = 512, 128, 64, 128, 640
SCALE = (NOPE + ROPE) ** -0.5


def decode_case(h, qmul, entries=32, nb=1024, rows_n=64):
    ks = jax.random.split(jax.random.key(52), 2)
    pool = jax.random.normal(ks[0], (nb * BS, W), jnp.bfloat16)
    pool = pool.at[:, RANK + ROPE:].set(0)
    q = jax.random.normal(ks[1], (rows_n, h, W), jnp.bfloat16) * qmul
    rng = np.random.default_rng(52)
    tables = np.zeros((rows_n, entries), np.int32)
    pos = np.full(rows_n, -1, np.int32)
    free = iter(rng.permutation(nb - 1) + 1)
    for r in rng.permutation(rows_n)[:rows_n - 6]:
        n = int(rng.integers(1, min(entries, 14) + 1))
        tables[r, :n] = [next(free) for _ in range(n)]
        pos[r] = (n - 1) * BS + rng.integers(0, BS)
    slot = jnp.arange(rows_n, dtype=jnp.int32)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    walk = jax.jit(lambda q, pool: lf.latent_decode_attention(
        q, pool, tables, slot, pos, block_size=BS, value_dim=RANK,
        scale=SCALE, interpret=False))
    gather = jax.jit(lambda q, pool: absorbed_read_xla(
        q, pool, tables, slot, pos, BS, RANK, SCALE))
    got, want = walk(q, pool), gather(q, pool)
    real = np.asarray(pos) >= 0
    g, w = np.asarray(got, np.float32)[real], np.asarray(want,
                                                         np.float32)[real]
    t0 = time.perf_counter()
    for _ in range(10):
        out = walk(q, pool)
    out.block_until_ready()
    us = (time.perf_counter() - t0) / 10 * 1e6
    return float(np.max(np.abs(g - w)) / np.max(np.abs(w))), us


def prefill_case(h, qmul, start, rows, entries=32):
    nb = entries + 40
    ks = jax.random.split(jax.random.key(45), 4)
    pool = jax.random.normal(ks[0], (nb * BS, W), jnp.bfloat16)
    pool = pool.at[:, RANK + ROPE:].set(0)
    w_kvb = (jax.random.normal(ks[1], (RANK, h * (NOPE + VD)), jnp.float32)
             * RANK ** -0.5).astype(jnp.bfloat16)
    q_nope = qmul * jax.random.normal(ks[2], (rows, h, NOPE), jnp.bfloat16)
    q_pe = qmul * jax.random.normal(ks[3], (rows, h, ROPE), jnp.bfloat16)
    q_cat = jnp.concatenate(
        [q_nope, q_pe, jnp.zeros((rows, h, 128 - ROPE), jnp.bfloat16)], -1)
    rng = np.random.default_rng(45)
    table = rng.permutation(nb - 1)[:entries].astype(np.int32) + 1
    tables = np.zeros((8, entries), np.int32)
    tables[5] = table
    tables, slot = jnp.asarray(tables), jnp.full((rows,), 5, jnp.int32)
    pos = jnp.arange(start, start + rows, dtype=jnp.int32)

    @jax.jit
    def kernels(q_cat, pool, w_kvb):
        kv, plan = lf.latent_expand(pool, w_kvb, tables, slot, pos,
                                    block_size=BS, tile_q=TILE, rank=RANK,
                                    interpret=False)
        return lf.latent_prefill_attention(
            q_cat, kv, plan, pos, block_size=BS, tile_q=TILE, nope=NOPE,
            v_dim=VD, scale=SCALE, interpret=False)

    @jax.jit
    def errors(got, pool, q_nope, q_pe, w_kvb):
        ctx = pool[(jnp.asarray(table)[:, None] * BS
                    + jnp.arange(BS)[None, :]).reshape(-1)]
        kv = jnp.dot(ctx[:, :RANK], w_kvb,
                     preferred_element_type=jnp.float32
                     ).astype(pool.dtype).reshape(-1, h, NOPE + VD)
        k_pe = ctx[:, RANK:RANK + ROPE]
        keep = jnp.arange(ctx.shape[0])[None, :] <= pos[:, None]

        def one(args, low):
            qn, qp, k, v = args
            s = (jnp.einsum("td,cd->tc", qn, k,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("td,cd->tc", qp, k_pe,
                              preferred_element_type=jnp.float32)) * SCALE
            s = jnp.where(keep, s, -1e30)
            p = jax.nn.softmax(s.astype(jnp.bfloat16) if low else s, axis=-1)
            return jnp.einsum("tc,cd->td", p.astype(v.dtype), v,
                              preferred_element_type=jnp.float32)

        heads = (q_nope.transpose(1, 0, 2), q_pe.transpose(1, 0, 2),
                 kv[..., :NOPE].transpose(1, 0, 2),
                 kv[..., NOPE:].transpose(1, 0, 2))
        want = jax.lax.map(lambda a: one(a, False), heads)
        low = jax.lax.map(lambda a: one(a, True), heads)
        got = got.astype(jnp.float32).transpose(1, 0, 2)
        top = jnp.max(jnp.abs(want))
        per_head = jnp.max(jnp.abs(got - want), axis=(1, 2)) / top
        return (jnp.max(jnp.abs(got - want)) / top,
                jnp.max(jnp.abs(low - want)) / top, per_head)

    got = kernels(q_cat, pool, w_kvb)
    err, low, per_head = errors(got, pool, q_nope, q_pe, w_kvb)
    return float(err), float(low), np.asarray(per_head)


def main():
    print(jax.devices(), flush=True)
    for h in (16, 64):
        for qmul in (0.2, 1.0, 4.0):
            err, us = decode_case(h, qmul)
            print(f"decode walk h={h} q x {qmul}: max |got - want| / max "
                  f"|want| = {err:.5f}; {us:.0f} us a call", flush=True)
    for h in (16, 64):
        for qmul in (1.0, 4.0, 12.0):
            for start, rows in ((0, 1024), (1024, 512), (2048, 1024)):
                err, low, per_head = prefill_case(h, qmul, start, rows)
                worst = np.argsort(-per_head)[:4]
                print(f"expand + prefill h={h} q x {qmul} rows {start}-"
                      f"{start + rows}: err {err:.5f} (a bf16 softmax reads "
                      f"{low:.5f}); worst heads "
                      f"{[(int(i), round(float(per_head[i]), 4)) for i in worst]}",
                      flush=True)


if __name__ == "__main__":
    main()
