"""PR 50, call 1: the three steps of the sparse read alone at the published
widths, one layer: 1,024 rows of a chunk (8 tiles of 128 that share a table)
at 8 k and 32 k positions, and 16 one-token rows at 14 k.  Milliseconds a
call (median of 5 after a warm-up), the selected set of each against a
stable sort of the same scores, and two candidates for the selection of the
tile rows: the radix select the program uses, and ``lax.top_k`` with the
mask built from its last value."""
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
H, W, RANK, HI, DI = 64, 640, 512, 32, 128
CASES = {"chunk_8k": (8, 128, 8192), "chunk_32k": (8, 128, 32768),
         "rows_14k": (16, 1, 14000)}
if os.environ.get("PR50_TINY"):         # the CPU rehearsal of this script
    BS, BLOCKS, ENTRIES, K, H = 16, 64, 40, 64, 4
    CASES = {"chunk": (2, 16, 500), "rows": (4, 1, 4300)}
SCALE = 256 ** -0.5


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return out, 1e3 * float(np.median(ts))


def case(groups: int, rows: int, end: int, seed: int = 0):
    """``groups`` groups of ``rows`` rows; a tile's rows end at ``end``."""
    ks = jax.random.split(jax.random.key(seed), 6)
    pool = jax.random.normal(ks[0], (BLOCKS * BS, W), jnp.bfloat16)
    idx_pool = jax.random.normal(ks[1], (BLOCKS * BS, DI), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    if rows > 1:        # one sequence, its tiles one after another
        table = rng.permutation(np.arange(1, BLOCKS))[:ENTRIES]
        tables = np.tile(table, (groups, 1))
        pos = end - groups * rows + np.arange(groups * rows).reshape(
            groups, rows)
    else:
        tables = np.stack([rng.permutation(np.arange(1, BLOCKS))[:ENTRIES]
                           for _ in range(groups)])
        pos = (end + 517 * np.arange(groups) - 4000).reshape(groups, 1)
    q_idx = jax.random.normal(ks[2], (groups, rows, HI, DI), jnp.bfloat16)
    w_idx = jax.random.normal(ks[3], (groups, rows, HI), jnp.float32)
    q_cat = jax.random.normal(ks[4], (groups, rows, H, W), jnp.bfloat16)
    return (pool, idx_pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32), q_idx, w_idx, q_cat)


def sets_equal(scores, pos, mask) -> bool:
    """``mask [N, C]`` against a stable sort of ``scores`` (on the host)."""
    scores, pos, mask = (np.asarray(a) for a in (scores, pos, mask))
    for row in range(0, scores.shape[0], max(1, scores.shape[0] // 16)):
        order = np.argsort(-scores[row], kind="stable")[:K]
        want = np.zeros(scores.shape[1], bool)
        want[order] = True
        want &= np.arange(scores.shape[1]) <= pos[row]
        if not (want == mask[row]).all():
            return False
    return True


def main():
    out = {}
    score = jax.jit(lambda q, w, ip, t, p: sl.index_scores(
        q, w, ip, t, p, block_size=BS))
    for name, (g, r, end) in CASES.items():
        pool, idx_pool, tables, pos, q_idx, w_idx, q_cat = case(g, r, end)
        scores, ms_score = timed(score, q_idx, w_idx, idx_pool, tables, pos)
        res = {"index_score_ms": ms_score}
        c = scores.shape[-1]
        flat, fpos = scores.reshape(g * r, c), pos.reshape(g * r)
        place = jnp.arange(c, dtype=jnp.int32)
        if r > 1:
            radix = jax.jit(lambda s, p: sl.select_threshold(
                sl.sort_key(s), K, live=jnp.max(p) + 1))
            (thr, cut), res["radix_select_ms"] = timed(radix, flat, fpos)
            mask = sl.selected(sl.sort_key(flat), place, thr, cut) \
                & (place[None] <= fpos[:, None])
            res["radix_set_is_the_sorts"] = sets_equal(flat, fpos, mask)

            def by_topk(s):
                vals, idx = jax.lax.top_k(s, K)
                last = vals[:, -1:]
                tie_cut = jnp.max(jnp.where(vals == last, idx, -1), axis=1)
                return last[:, 0], tie_cut
            (last, tie_cut), res["lax_top_k_ms"] = timed(
                jax.jit(by_topk), flat)
            mask2 = (flat > last[:, None]) | (
                (flat == last[:, None]) & (place[None] <= tie_cut[:, None]))
            res["top_k_set_is_the_sorts"] = sets_equal(
                flat, fpos, mask2 & (place[None] <= fpos[:, None]))
            read = jax.jit(lambda q, p, t, ps, s: sl.masked_latent_read(
                q, p, t, ps, sl.sort_key(s), *[
                    a.reshape(g, r) for a in sl.select_threshold(
                        sl.sort_key(s).reshape(g * r, c), K,
                        live=jnp.max(ps) + 1)],
                block_size=BS, rank=RANK, scale=SCALE))
            _, both = timed(read, q_cat, pool, tables, pos, scores)
            res["select_and_masked_read_ms"] = both
        else:
            topk = jax.jit(lambda s: sl.select_topk(s, K))
            sel, res["lax_top_k_ms"] = timed(topk, flat)
            mask = np.zeros((g, c), bool)
            np.put_along_axis(mask, np.asarray(sel), True, axis=1)
            mask &= np.arange(c)[None] <= np.asarray(fpos)[:, None]
            res["top_k_set_is_the_sorts"] = sets_equal(flat, fpos, mask)
            read = jax.jit(lambda q, p, t, ps, s: sl.gathered_latent_read(
                q, p, t, ps, s, block_size=BS, rank=RANK, scale=SCALE))
            _, res["gathered_read_ms"] = timed(
                read, q_cat[:, 0], pool, tables, pos[:, 0], sel)
        out[name] = res
        print(name, json.dumps(res), flush=True)
    os.makedirs("chiprun_out/pr50", exist_ok=True)
    with open("chiprun_out/pr50/call01_steps.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
