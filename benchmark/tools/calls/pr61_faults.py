"""PR 61: does the runner's logits check see the faults a model with window
layers whose cache row is a latent, beside sparse-indexed latent layers, can
have?  The check of ``serve_ragged.py`` (6,144 prompt tokens in six chunks of
1,024: past the 513-token window, three times ``index_topk``, over chunk and
block edges; then 8 decode steps through both pools; against the float32
reference) on the cell's engine, a line a variant.

``clean``: the program as it is.  The faults are
``tests/unit/dots3_note_faults.py``'s (its module doc says what each is),
what ``tests/unit/test_ragged_dots3_note.py`` applies at tiny sizes on the
CPU.  ``band_released_early`` runs with the tables' debug validation off
(``DEEPSPEED_TPU_RAGGED_DEBUG=0`` for the whole process: with it on the
validation refuses the table by name before any program runs).
``window_reads_global`` is NOT applied here (``CHIP_SKIP``: block ids past the
window pool's end halt the core).

And one control that is no fault of the program: ``reference_low_precision``
is the unchanged engine against the float32 reference computed on weights cut
to 3 mantissa bits (``pr39_faults._LowPrecisionReference``): what a
computation below bf16 reads, which has to be over the limit too.

    python3 benchmark/tools/calls/pr61_faults.py [ONLY=a,b] [NAME=value] <seed> [<seed> ...]

``NAME=value`` sets a seeding constant of ``benchmark/families/dots3_note.py``
for this process (``ATTN_OUT``, ``Q_SCALE``: how the values in that file were
chosen).

Exits 1 unless ``clean`` is under ``LOGIT_TOL`` and every fault and the
control over it; a fault in ``READINGS`` is printed and counted for nothing.
"""

import gc
import os

os.environ.setdefault("DEEPSPEED_TPU_RAGGED_DEBUG", "0")

import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [_CHECKOUT, os.path.join(_CHECKOUT, "tests", "unit")]

import numpy as np                                      # noqa: E402
from dots3_note_faults import FAULTS, fault             # noqa: E402

CELL = "serve-dots3-notes-closed48"
#: NOT applied on the chip: a sliding layer that reads through the global
#: group's table hands the banded walk block ids past the window pool's end
#: (4,800 blocks against 297), and the copy halts the core ("Accelerator
#: device halted prematurely", PR 61, call 1): the chip sees it at once and
#: takes the process with it.  ``test_ragged_dots3_note.py`` reads it.
CHIP_SKIP = ("window_reads_global",)
#: what the seeded check cannot see (PERF.md section 6, PR 61): printed,
#: counted for nothing
READINGS = ("window_minus_1", "window_plus_1", "rescales_swapped")
FAULTS = tuple(f for f in FAULTS if f not in CHIP_SKIP + READINGS)
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
    for seed in seeds or [6100000061]:
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
