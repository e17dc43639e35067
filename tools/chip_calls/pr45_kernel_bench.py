"""PR 45, the expanded latent read alone on the chip: microseconds a call of
``latent_prefill_attention`` at the Moonlight cell's shape (a 1,024-token
chunk from each of three positions over a 60-entry table, 16 heads, tile
128), on the tree named by ``--tree`` (the parent's copy under
``build/parent`` or this one), through this tree's
``tools/kernel_selftest.py::latent_prefill_cell_case``.

    python tools/chip_calls/pr45_kernel_bench.py --tree build/parent --out parent.json
    python tools/chip_calls/pr45_kernel_bench.py --out change.json "{}" "{'kb': 2}" "{'kb': 8}"

Arguments: variants of the kernel (a private dict ``latent_flash._VARIANT``
that the kernel read while the PR's measurements ran: ``kb``, ``concat``,
``group``, ``next_fill``, ``pair`` (a query block of two tiles),
``mask_all``, ``cols``, ``unroll``; the tree as committed has none and the
script then refuses them)."""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
STARTS = (0, 2048, 6144)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--out", required=True)
    ap.add_argument("variants", nargs="*")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    spec = importlib.util.spec_from_file_location(
        "pr45_selftest", os.path.join(HERE, "tools", "kernel_selftest.py"))
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    from deepspeed_tpu.inference.v2.kernels import latent_flash
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    out = {}
    for variant in [eval(v) for v in args.variants] or [{}]:
        if variant:
            if not hasattr(latent_flash, "_VARIANT"):
                raise SystemExit(f"{args.tree}: this tree's kernel has no "
                                 f"variants (only PR 45's working trees had)")
            import jax

            jax.clear_caches()            # the variant is read when traced
            latent_flash._VARIANT.clear()
            latent_flash._VARIANT.update(variant)
        t0 = time.perf_counter()
        try:
            res = selftest.latent_prefill_cell_case(starts=STARTS)
        except Exception as e:  # noqa: BLE001 - one variant's failure
            res = {"error": f"{type(e).__name__}: {e}"[:600]}
        res["wall_s"] = round(time.perf_counter() - t0, 1)
        out[str(variant)] = res
        print(variant, json.dumps(res), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
