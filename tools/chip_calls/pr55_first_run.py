"""PR 55, one-off for the chip: does a program that holds the new kernel cost more to START than the parent's?
For each named form (``pr55_candidates.form``): six calls at the cell's shape in one jitted program; seconds to
lower and compile (or to read from the compile cache, when the process before this one compiled it), of the first
execution, and of the tenth.  Run it twice in one chip call: the second process reads what the first compiled.

    python3 tools/chip_calls/pr55_first_run.py committed parent"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import pr55_candidates as cand                  # noqa: E402  (puts the repo's root on the path)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from deepspeed_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from deepspeed_tpu.utils.platform import require_tpu                 # noqa: E402

require_tpu("pr55_first_run")
enable_compile_cache()
rows, h, d, slots, tile = 1024, 32, 128, 32, 128
ks = jax.random.split(jax.random.key(1), 6)
ops = (jax.random.normal(ks[0], (rows, h, d)) * 0.01, jax.random.normal(ks[1], (rows, h, d)) * 0.09,
       jax.random.normal(ks[2], (rows, h, d)), -0.05 * jnp.abs(jax.random.normal(ks[3], (rows, h))),
       jax.nn.sigmoid(jax.random.normal(ks[4], (rows, h))), jnp.asarray([9, 9, 9, 20, 20, 20, 4, slots], jnp.int32),
       jnp.asarray([1, 0, 0, 0, 0, 0, 1, 0], bool))
pool = jax.random.normal(ks[5], (slots + 1, h, d, d))
for name in sys.argv[1:]:
    call = cand.form(name)

    def stacked(pools, *rest, call=call):
        y, new = 0.0, []
        for p in pools:
            o, p = call(p, *rest, tile)
            y, new = y + o, new + [p]
        return y, new

    pools = [pool + 0.0 for _ in range(6)]
    jax.block_until_ready(pools)
    t0 = time.perf_counter()
    run = jax.jit(stacked, donate_argnums=0).lower(pools, *ops).compile()
    t1 = time.perf_counter()
    y, pools = run(pools, *ops)
    y.block_until_ready()
    t2 = time.perf_counter()
    for _ in range(9):
        y, pools = run(pools, *ops)
    y.block_until_ready()
    t3 = time.perf_counter()
    y, pools = run(pools, *ops)
    y.block_until_ready()
    t4 = time.perf_counter()
    print(json.dumps({"form": name, "compile_or_read_s": round(t1 - t0, 3), "first_run_s": round(t2 - t1, 4),
                      "tenth_run_s": round(t4 - t3, 4)}), flush=True)
