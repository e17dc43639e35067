"""PR 51, call 1: the tile rows' read alone at the published widths, one
layer: XLA's composition (``masked_latent_read``) against the Mosaic kernel
(``sparse_tile_read``) on the same operands, milliseconds a call (median of
5 after a warm-up) and the largest difference of the two results.  Three
batches: a 1,024-row chunk whose rows end at 8 k, one at 32 k, and the end
of one prompt (3 tiles at 5.3 k) beside the start of the next (5 tiles from
position 0).  The kernel at several (keys a step, heads a group):
``VARIANTS``; the first is what the module ships.  (Calls 1 and 3 also
varied the heads a product took, written out and in a loop, in a kernel that
still had that loop: ``pr51_results/call01_kernel.json``,
``call03_kernel.json``; the module kept one product and one softmax update
a step, ``call05_kernel.json``, which chose 256 keys.)

    python3 benchmark/tools/calls/pr51_call01_kernel.py [variant ...]
"""
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402

from deepspeed_tpu.inference.v2.kernels import sparse_latent as sl  # noqa: E402

BS, BLOCKS, ENTRIES, K = 128, 2048, 260, 2048
R, H, W, RANK = 128, 64, 640, 512
#: (tiles of a sequence, its last position) a batch
CASES = {"chunk_8k": [(8, 8191)], "chunk_32k": [(8, 32767)],
         "end_5k_and_start": [(3, 5300), (5, 639)]}
VARIANTS = ["256,16", "512,16", "1024,16", "256,32", "256,8"]
if os.environ.get("PR51_TINY"):         # the CPU rehearsal of this script
    BS, BLOCKS, ENTRIES, K, R, H, W, RANK = 16, 64, 40, 64, 16, 4, 128, 32
    CASES = {"chunk": [(2, 500)], "two": [(1, 300), (2, 31)]}
    VARIANTS = ["64,4", "128,2"]
SCALE = 256 ** -0.5


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return out, 1e3 * float(np.median(ts))


def case(chunks, seed: int = 0):
    """A call's operands: a sequence a chunk, its tiles one after another
    up to its last position (the last tile may hold pad rows)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    rng = np.random.default_rng(seed)
    pool = jax.random.normal(ks[0], (BLOCKS * BS, W), jnp.bfloat16)
    tables, pos = [], []
    for tiles, last in chunks:
        table = rng.permutation(np.arange(1, BLOCKS))[:ENTRIES]
        # tile-aligned: from ``last`` back, or from 0 with pad rows behind
        p = max(last + 1 - tiles * R, 0) + np.arange(tiles * R)
        tables += [table] * tiles
        pos.append(np.where(p <= last, p, -1).reshape(tiles, R))
    tables = jnp.asarray(np.stack(tables), jnp.int32)
    pos = jnp.asarray(np.concatenate(pos), jnp.int32)
    g = pos.shape[0]
    c = -(-ENTRIES * BS // sl.KEY_BLOCK) * sl.KEY_BLOCK \
        if ENTRIES * BS > sl.KEY_BLOCK else ENTRIES * BS
    scores = jax.random.normal(ks[1], (g, R, c), jnp.float32)
    scores = jnp.where(jnp.arange(c)[None, None] <= pos[..., None], scores,
                       -jnp.inf)
    key = sl.sort_key(scores)
    thr, cut = jax.jit(lambda k, p: sl.select_threshold(
        k.reshape(g * R, c), K, live=jnp.max(p) + 1))(key, pos)
    q_cat = jax.random.normal(ks[2], (g, R, H, W), jnp.bfloat16)
    return (q_cat, pool, tables, pos, key, thr.reshape(g, R),
            cut.reshape(g, R))


def main(variants):
    out = {}
    kw = dict(block_size=BS, rank=RANK, scale=SCALE)
    for name, chunks in CASES.items():
        args = case(chunks)
        want, ms = timed(jax.jit(
            lambda *a: sl.masked_latent_read(*a, **kw)), *args)
        res = {"xla_ms": ms}
        for v in variants:
            sl._STEP_KEYS, sl._HEAD_GROUP = map(int, v.split(","))
            sl.sparse_tile_read.clear_cache()   # traced at other constants
            try:
                got, ms = timed(jax.jit(
                    lambda *a: sl.sparse_tile_read(*a, **kw)), *args)
                res[v] = {"ms": ms, "max_diff": float(
                    jnp.max(jnp.abs(got - want))),
                    "finite": bool(jnp.all(jnp.isfinite(got)))}
            except Exception as e:          # a variant the chip refuses
                res[v] = {"error": repr(e)[:300]}
            print(name, v, json.dumps(res[v]), flush=True)
        res["max_abs"] = float(jnp.max(jnp.abs(want)))
        out[name] = res
        print(name, json.dumps(res), flush=True)
    os.makedirs("chiprun_out/pr51", exist_ok=True)
    with open("chiprun_out/pr51/call01_kernel.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:] or VARIANTS)
