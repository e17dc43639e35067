"""PR 53, one-off for the chip: what does the forward grouped GEMM's weight ring give, and at how many slots?

``tools/kernel_selftest.gmm_share_case`` (the six shapes of ``GMM_SHARE_CELLS``; seeded routing, microseconds
a call beside the least and beside ``gmm_call_model``'s two figures) with ``ops/grouped_gemm.py``'s forward
call in four forms:

* ``parent``: the expert weights on ``pallas_call``'s grid pipeline, fetched one grid step ahead: the kernel
  as it was before PR 53 (``tests/unit/gmm_grid_pipeline.py``, the oracle of the ring's bit-for-bit tests);
* ``ring2``: the committed kernel with a ring of two slots (the parent's VMEM footprint; one block ahead);
* ``ring3``: three slots (two blocks ahead) at every shape, the limit raised where that takes it;
* ``change``: the committed rule (``_ring_slots``: a third slot where the budget the tiles came under holds it).

Every form's first call is also compared with the parent form's, bit for bit (``max_diff_vs_parent``).
Prints one JSON line a form; ``--rehearse`` prints each form's tiles, slots and working set, runs every form
once at a tiny shape in interpret mode (no chip) and prints the largest difference between them.
Nothing here is imported by the program.
"""

from __future__ import annotations

import functools
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tools"))
sys.path.insert(0, os.path.join(_ROOT, "tests", "unit"))


def _forms():
    import jax

    from gmm_grid_pipeline import grid_pipeline_gmm

    from deepspeed_tpu.ops import grouped_gemm as gg

    committed = gg._gmm_fwd_kernel_call
    parent = jax.jit(grid_pipeline_gmm, static_argnames=("tile_m", "tile_n", "interpret"))
    return {"parent": parent,
            "ring2": functools.partial(committed, slots=2),
            "ring3": functools.partial(committed, slots=3),
            "change": committed}


def _slots(name, gg, tm, k, tn):
    return {"parent": 2, "ring2": 2, "ring3": 3}.get(name) or gg._ring_slots(tm, k, tn)


def _rehearse(names, forms):
    import jax.numpy as jnp
    import kernel_selftest as ks
    import numpy as np

    from deepspeed_tpu.ops import grouped_gemm as gg

    for name in names:
        picks = {}
        for cell, (t, k_top, _, held, h, f) in ks.GMM_SHARE_CELLS.items():
            m = -(-t * k_top // 128) * 128
            for call, (k, n) in (("gate_up", (h, f)), ("down", (f, h))):
                tm, tn = gg._pick_tiles(m, k, n, held)
                slots = _slots(name, gg, tm, k, tn)
                picks[f"{cell}.{call}"] = [tm, tn, slots, round(gg._forward_vmem(tm, k, tn, 2, slots) / 2 ** 20, 2)]
        print(json.dumps({"form": name, "tiles_slots_mib": picks}))
    rng = np.random.default_rng(53)
    sizes = [300, 0, 0, 40, 0, 10]          # 350 of 512 rows: dead units at the end, three n-tiles
    lhs = jnp.asarray(rng.standard_normal((512, 64)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((6, 64, 384)), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    outs = {name: np.asarray(forms[name](lhs, rhs, gs, tile_m=128, tile_n=128, interpret=True).astype(jnp.float32))
            for name in names}
    first = outs[names[0]]
    print(json.dumps({"rehearsal": "interpret mode, [512, 64] x [6, 64, 384]",
                      "max_diff_vs_" + names[0]: {n: float(np.max(np.abs(o - first))) for n, o in outs.items()}}))


def main():
    import kernel_selftest as ks

    from deepspeed_tpu.ops import grouped_gemm as gg

    forms = _forms()
    names = [a for a in sys.argv[1:] if not a.startswith("--")] or list(forms)
    if "--rehearse" in sys.argv:
        return _rehearse(names, forms)
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.utils.platform import require_tpu

    require_tpu("pr53_probe.py")
    # one shape with reuse, an empty expert and dead units, every form against the parent's bits
    keys = jax.random.split(jax.random.key(53), 2)
    lhs = jax.random.normal(keys[0], (1024, 2048), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (8, 2048, 1536), jnp.bfloat16) * 2048 ** -0.5
    gs = jnp.asarray([300, 0, 129, 1, 0, 260, 128, 77], jnp.int32)
    want = forms["parent"](lhs, rhs, gs, tile_m=128, tile_n=768, interpret=False)
    committed = gg._gmm_fwd_kernel_call
    for name in names:
        gg._gmm_fwd_kernel_call = forms[name]
        try:
            got = forms[name](lhs, rhs, gs, tile_m=128, tile_n=768, interpret=False)
            diff = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))))
            print(json.dumps({"form": name, "max_diff_vs_parent": diff, **ks.gmm_share_case(3e-2)}), flush=True)
        except Exception as e:  # a form the compiler refuses is a finding, not the probe's end
            print(json.dumps({"form": name, "error": f"{type(e).__name__}: {e}"[:600]}), flush=True)
        finally:
            gg._gmm_fwd_kernel_call = committed


if __name__ == "__main__":
    main()
