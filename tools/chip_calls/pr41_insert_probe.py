"""PR 41, chip call 11: the row insert on a flat bf16 pool at the cells' shapes, ``pool.at[dest].set(rows)`` (what calls 1-10
ran) against ``ragged_llama.insert_kv`` (the two-index form on the ``[rows / 16, 16, lanes]`` view).  32 inserts a program
(a fori_loop, the destinations shifted a row an iteration), 5 programs back to back, host clock: microseconds an insert.

    python3 tools/chip_calls/pr41_insert_probe.py <out.json>
"""
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from deepspeed_tpu.inference.v2.model_implementations.ragged_llama import insert_kv  # noqa: E402

#: pool rows, tokens a forward, lanes: Trinity's window pool under a full mixed tick, OLMoE's and the chat cell's pool
#: under a mixed tick and a decode tick
SHAPES = {"trinity_win_T1056": (140288, 1056, 1024), "olmoe_T544": (24576, 544, 2048), "olmoe_decode": (24576, 32, 2048),
          "mistral_T1056": (20480, 1056, 1024), "mistral_decode": (20480, 32, 1024)}
N, REPEATS = 32, 5


def plain(pool, dest, x):
    return pool.at[dest].set(x)


def two_index(pool, dest, x):
    return insert_kv({"k": pool, "v": pool}, dest, x[:, None, :], x[:, None, :])[0]


results = {}
for name, (rows, t, lanes) in SHAPES.items():
    rng = np.random.default_rng(41)
    # a forward's destinations: chunks of consecutive rows and single rows, pads on the trash row 0
    dest = np.concatenate([np.zeros(t // 8, np.int64), rng.integers(128, rows - t, 1) + np.arange(t - t // 8 - t // 8),
                           rng.integers(128, rows, t // 8)])[:t]
    dest = jnp.asarray(dest, jnp.int32)
    x = jax.random.normal(jax.random.key(1), (t, lanes), jnp.bfloat16)
    results[name] = {}
    for form, f in (("plain", plain), ("two_index", two_index)):
        run = jax.jit(lambda pool, dest, x, f=f: jax.lax.fori_loop(
            0, N, lambda i, p: f(p, (dest + i) % rows, x), pool), donate_argnums=0)
        pool = jnp.zeros((rows, lanes), jnp.bfloat16)
        pool = run(pool, dest, x)
        pool.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            pool = run(pool, dest, x)
        pool.block_until_ready()
        results[name][form] = round((time.perf_counter() - t0) / REPEATS / N * 1e6, 1)
        results[name][form + "_sum"] = float(jnp.sum(pool.astype(jnp.float32)))
        del pool
    print("INSERT", name, json.dumps(results[name]), flush=True)
out = sys.argv[1]
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
with open(out, "w") as f:
    json.dump(results, f, indent=1)
