"""PR 56: does the runner's logits check see the faults a post-norm block of
Gated DeltaNet layers (write strengths up to 2) and position-free attention
can have?  The check of ``serve_ragged.py`` (1,536 prompt tokens in two
chunks of 1,024 and 512, so the carried state and the convolution tail
cross a chunk boundary, then 8 decode steps, against the float32 reference)
on the cell's engine, a line a variant.

``clean``: the program as it is.  The faults are
``tests/unit/olmo_hybrid_faults.py``'s (what ``test_ragged_olmo_hybrid.py``
applies at tiny sizes on the CPU, where every one is seen at float32):
``carry_dropped``, ``tail_dropped``, ``beta_unit``, ``pre_norm``,
``rotary``, and two that lower the precision the configuration states:
``state_bf16``, ``products_bf16``.  And one control that is no fault of the
program: ``reference_low_precision`` is the unchanged engine against the
float32 reference computed on weights cut to 3 mantissa bits
(``pr39_faults._LowPrecisionReference``): what a computation below bf16
reads, which has to be over the limit too.

    python3 benchmark/tools/calls/pr56_faults.py [ONLY=a,b] <seed> [<seed> ...]

Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it; a fault listed in ``UNSEEN`` is printed and counted for
nothing (PERF.md says which CPU test sees it).
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [_CHECKOUT, os.path.join(_CHECKOUT, "tests", "unit")]

import numpy as np                                      # noqa: E402
from olmo_hybrid_faults import FAULTS, fault            # noqa: E402

CELL = "serve-olmohybrid-evalgen-closed128"
#: not reliably over the limit on seeded weights: printed, counted for
#: nothing
UNSEEN = ("state_bf16", "products_bf16")
#: no fault: the reference below the stated precision
CONTROLS = ("reference_low_precision",)


def main(argv) -> int:
    from benchmark.lib import device, spec
    from benchmark.runners import serve_ragged
    from benchmark.tools.calls.pr39_faults import (_LowPrecisionReference,
                                                   cell_engine)

    bench = spec.benchmark_spec()
    cfg = spec.config_for(bench, spec.cell(bench, CELL))
    device.claim_devices(1)
    device.enable_compile_cache()
    family = spec.module("families", cfg["family"])
    reference = spec.module("reference", family.REFERENCE)
    sv = cfg["serve"]
    only, seeds = None, []
    for arg in argv:
        name, _, value = arg.partition("=")
        if name == "ONLY":
            only = value.split(",")
        else:
            seeds.append(int(arg))
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [5600000056]:
        for name in ("clean",) + FAULTS + CONTROLS:
            if only and name not in only:
                continue
            control = name in CONTROLS
            engine = None
            try:
                with fault("clean" if control else name):
                    engine = cell_engine(cfg, family, seed)
                    gap = serve_ragged._check_logits(
                        engine, reference,
                        _LowPrecisionReference(family) if control
                        else family, cfg, seed,
                        int(sv["check_prompt_tokens"]),
                        int(sv["check_decode_tokens"]))
            except NotImplementedError as e:    # the compiler's refusal
                print(f"seed {seed} {name}: cannot be built: {e}",
                      flush=True)
                continue
            finally:
                del engine
            gc.collect()    # the step programs' closures hold the engine
            seen = (gap <= tol) if name == "clean" else (gap > tol)
            if name in UNSEEN:
                verdict = "a reading"
            else:
                bad += not seen
                verdict = "as expected" if seen else "NOT AS EXPECTED"
            if name == "clean":
                clean.append(gap)
            print(f"seed {seed} {name}: gap {gap:.5f} against {tol}: "
                  f"{verdict}", flush=True)
    if len(clean) > 1:
        mean, std = float(np.mean(clean)), float(np.std(clean, ddof=1))
        print(f"clean over {len(clean)} seeds: mean {mean:.5f} std "
              f"{std:.5f} max {max(clean):.5f}; mean + 4 std "
              f"{mean + 4 * std:.5f} against {tol}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
