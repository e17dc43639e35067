"""PR 49, the three bshd flash kernels alone on the chip at the two training
cells' shapes: ``tools/kernel_selftest.py::flash_train_case`` of THIS tree
over the kernels of the tree named by ``--tree`` (the parent's copy under
``build/parent``, or this one).

    python tools/chip_calls/pr49_kernel_bench.py --tree build/parent --out parent.json "{}" "{'DEFAULT_BLOCK_K': 512}"
    python tools/chip_calls/pr49_kernel_bench.py --out change.json "{}" "{'SUB_BLOCK_K': 512}"

Each argument is a variant: module constants of ``ops/flash_attention.py``
set before the case is traced (the tile sizes ``DEFAULT_BLOCK_Q`` /
``DEFAULT_BLOCK_K`` in either tree: form (b) of the issue, the grid's own
skipping; the sub-block sizes ``SUB_BLOCK_Q`` / ``SUB_BLOCK_K`` in this
one: form (a), the walk).  Calls 1-3 ran while this tree's causal q-tile was
still ``DEFAULT_BLOCK_Q``; as committed it is ``CAUSAL_BLOCK_Q``."""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--out", required=True)
    ap.add_argument("--cells", default="")
    ap.add_argument("variants", nargs="*")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    sys.path.insert(1, HERE)                 # benchmark/lib/costs.py
    spec = importlib.util.spec_from_file_location(
        "pr49_selftest", os.path.join(HERE, "tools", "kernel_selftest.py"))
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    import jax

    from deepspeed_tpu.ops import flash_attention as fa
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    assert os.path.abspath(fa.__file__).startswith(
        os.path.abspath(args.tree)), fa.__file__
    defaults = {}
    out = {}
    cells = args.cells.split(",") if args.cells else list(
        selftest.FLASH_TRAIN_CELLS)
    for variant in [eval(v) for v in args.variants] or [{}]:
        jax.clear_caches()                # the constants are read when traced
        for name, value in defaults.items():
            setattr(fa, name, value)
        for name, value in variant.items():
            defaults.setdefault(name, getattr(fa, name))
            setattr(fa, name, value)
        for cell in cells:
            t0 = time.perf_counter()
            try:
                res = selftest.flash_train_case(
                    *selftest.FLASH_TRAIN_CELLS[cell])
            except Exception as e:  # noqa: BLE001 - one shape's failure
                res = {"error": f"{type(e).__name__}: {e}"[:600]}
            res["wall_s"] = round(time.perf_counter() - t0, 1)
            out[f"{cell} {variant}"] = res
            print(cell, variant, json.dumps(res), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
