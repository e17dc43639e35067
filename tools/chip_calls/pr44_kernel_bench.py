"""PR 44, the tiled chunk read alone on the chip: microseconds a call of
``paged_prefill_attention`` at the serving cells' shapes, on the tree named
by ``--tree`` (the parent's copy under ``build/parent`` or this one), through
this tree's ``tools/kernel_selftest.py::prefill_chunk_case``.

    python tools/chip_calls/pr44_kernel_bench.py --tree build/parent --out parent.json steps
    python tools/chip_calls/pr44_kernel_bench.py --out change.json cells "{'kb': 2}" "{'kb': 8}"

``steps``: the four readings that split a call of the accepted kernel into
live and skipped grid steps (Trinity's shapes; a table of 200 and of 100
entries under the same band, a global layer at two context lengths).
``cells``: every shape of ``PREFILL_CELLS``, checked against the XLA read.
Further arguments: variants of the kernel (a private dict
``blocked_flash._VARIANT`` that the kernel read while the PR's measurements
ran: ``kb``, ``mask_all``, ``scale_scores``; the tree as committed has none and
the script then refuses them)."""

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

STEPS = {"swa_200": (48, 8, 128, 200, 4096, (6144, 24000)),
         "swa_100": (48, 8, 128, 100, 4096, (6144,)),
         "full_200": (48, 8, 128, 200, None, (6144, 24000)),
         "full_100": (48, 8, 128, 100, None, (6144,))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=HERE)
    ap.add_argument("--out", required=True)
    ap.add_argument("what", choices=("steps", "cells", "all"))
    ap.add_argument("variants", nargs="*")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    spec = importlib.util.spec_from_file_location(
        "pr44_selftest", os.path.join(HERE, "tools", "kernel_selftest.py"))
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    from deepspeed_tpu.inference.v2.kernels import blocked_flash
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cases = {}
    if args.what in ("steps", "all"):
        cases.update(STEPS)
    if args.what in ("cells", "all"):
        cases.update(selftest.PREFILL_CELLS)
    out = {}
    for variant in [eval(v) for v in args.variants] or [{}]:
        if variant:
            if not hasattr(blocked_flash, "_VARIANT"):
                raise SystemExit(f"{args.tree}: this tree's kernel has no "
                                 f"variants (only PR 44's working trees had)")
            import jax

            jax.clear_caches()            # the variant is read when traced
            blocked_flash._VARIANT.clear()
            blocked_flash._VARIANT.update(variant)
        for name, shape in cases.items():
            t0 = time.perf_counter()
            try:
                res = selftest.prefill_chunk_case(
                    *shape, check=name in selftest.PREFILL_CELLS)
            except Exception as e:  # noqa: BLE001 - one shape's failure
                res = {"error": f"{type(e).__name__}: {e}"[:600]}
            res["wall_s"] = round(time.perf_counter() - t0, 1)
            out[f"{name} {variant}"] = res
            print(name, variant, json.dumps(res), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
