"""PR 54, no chip: the one training-attention route compiled for a described v5e, forward and both backward kernels.

`dot_product_attention` picks the kernel family from the head size; this compiles what it picks (with `on_tpu()` answered
as the chip would) at the two training cells' shapes and at the head groupings the folded family could be handed, 1,024
and 4,096 tokens.  `--family folded` forces the folded kernels at every geometry that has a lane-aligned grouping under
the guard of commit 444c052 (8 heads a group): that run is where `_FOLDED_MAX_HEADS_PER_BLOCK` 4 and 256 lanes come from.

    JAX_PLATFORMS=cpu python3 tools/chip_calls/pr54_aot_route.py [--family folded]
"""
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4", TPU_WORKER_HOSTNAMES="localhost", TPU_SKIP_MDS_QUERY="true")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
from jax.experimental import topologies                 # noqa: E402
from jax.sharding import SingleDeviceSharding           # noqa: E402

from deepspeed_tpu.ops import flash_attention as fa     # noqa: E402
from deepspeed_tpu.ops.attention import dot_product_attention  # noqa: E402

CELLS = [(8, 1024, 20, 20, 64, None), (1, 4096, 16, 4, 128, 4096)]
GEOMS = [(4, 4, 64), (12, 12, 64), (4, 2, 64), (8, 4, 64), (6, 2, 64), (8, 2, 64), (4, 4, 32), (8, 4, 32), (8, 8, 16),
         (16, 16, 96), (4, 4, 128), (8, 2, 128)]


def main() -> int:
    forced = sys.argv[1:] == ["--family", "folded"]
    if forced:
        fa._FOLDED_MAX_HEADS_PER_BLOCK, fa._FOLDED_MAX_LANES_PER_BLOCK = 8, 1024
    fa.on_tpu = lambda: True
    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    failed = 0
    for b, s, h, hkv, d, win in CELLS + [(2, s, *g, None) for g in GEOMS for s in (1024, 4096)]:
        q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)
        k = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one)

        def loss(q, k, v):
            return jnp.sum(dot_product_attention(q, k, v, causal=True, window=win).astype(jnp.float32) ** 2)

        head = f"{(b, s, h, hkv, d, win)} heads a group {fa.folded_heads_per_block(h, hkv, d)}:"
        try:
            low = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k)
            names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', low.as_text())))
            low.compile()
            print(head, "compiles", names, flush=True)
        except Exception as e:      # noqa: BLE001
            failed += 1
            print(head, "FAILED", str(e)[:160].replace("\n", " "), flush=True)
    return 1 if failed and not forced else 0


if __name__ == "__main__":
    sys.exit(main())
