"""PR 50: does the runner's logits check see the faults a model with a
learned sparse-attention indexer can have?  The check of ``serve_ragged.py``
(6,144 prompt tokens in six chunks of 1,024, three times ``index_topk``, then
8 decode steps, against the float32 reference) on the cell's engine, a line a
variant.

``clean``: the program as it is.  ``indexer_dropped``: every row reads every
cached position (the dense latent read under GLM-5's name).
``recent_topk``: the most recent ``index_topk`` positions in place of the
best-scored.  ``k_off_by_block``: ``index_topk`` less one block (1,920).
``indexer_rope_missing``: the indexer's queries and keys unrotated (the main
rope kept).  ``idx_row_fp8``: the ``idx_k`` leaf read at float8_e4m3's 3
mantissa bits, a precision below the bf16 the configuration states for it.

Two readings that count for nothing, because the seeded program cannot show
them (the configuration's ``assumed`` and PERF.md say so): ``w_scale_missing``
(``w`` without ``HI^-0.5 DI^-0.5``: a positive factor on every score of a row
changes no order, so the selected set is the same but for float32 roundings)
and ``k_norm_bias_dropped`` (the seeded bias is 0; the CPU tests draw it away
from 0 and see it).

And one control that is no fault of the program: ``reference_low_precision``
is the unchanged engine against the float32 reference computed on weights cut
to 3 mantissa bits (``pr39_faults._LowPrecisionReference``): what a
computation below bf16 reads, which has to be over the limit too.

    python3 benchmark/tools/calls/pr50_faults.py [ONLY=a,b] [NAME=value] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/glm_moe_dsa.py``
for this process (``ATTN_OUT``, ``Q_SCALE``: how the values in that file were
chosen).

Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it.  ``fault(name)`` is ``tests/unit/glm_dsa_faults.py``'s, what
``tests/unit/test_ragged_glm_dsa.py`` applies at tiny sizes on the CPU.
"""

import gc
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [_CHECKOUT, os.path.join(_CHECKOUT, "tests", "unit")]

import numpy as np                                      # noqa: E402
from glm_dsa_faults import fault                        # noqa: E402

CELL = "serve-glm5-longctx-closed16"
FAULTS = ("indexer_dropped", "recent_topk", "k_off_by_block",
          "indexer_rope_missing", "idx_row_fp8")
#: cannot be seen on seeded weights: printed, counted for nothing
READINGS = ("w_scale_missing", "k_norm_bias_dropped")
#: no fault: the reference below the stated precision
CONTROLS = ("reference_low_precision",)
SEEDING = ("ATTN_OUT", "Q_SCALE", "EXPERT_DOWN", "RESIDUAL_SCALE")


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
        elif value:
            setattr(family, name, float(value))
        else:
            seeds.append(int(arg))
    print("seeding: " + ", ".join(f"{k} {getattr(family, k)}"
                                  for k in SEEDING), flush=True)
    tol, bad, clean = serve_ragged.LOGIT_TOL, 0, []
    for seed in seeds or [5000000050]:
        for name in ("clean",) + FAULTS + READINGS + CONTROLS:
            if only and name not in only:
                continue
            control = name in CONTROLS
            with fault("clean" if control else name,
                       int(sv["block_size"])):
                engine = cell_engine(cfg, family, seed)
                gap = serve_ragged._check_logits(
                    engine, reference,
                    _LowPrecisionReference(family) if control else family,
                    cfg, seed, int(sv["check_prompt_tokens"]),
                    int(sv["check_decode_tokens"]))
            del engine
            gc.collect()    # the step programs' closures hold the engine
            seen = (gap <= tol) if name == "clean" else (gap > tol)
            if name in READINGS:
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
