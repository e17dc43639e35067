"""PR 36, one-off for the chip: which half of the forward's tile rule gives what?

``tools/kernel_selftest.gmm_share_case`` (the six shapes of ``GMM_SHARE_CELLS``: the LFM2 decode and
``T1152`` calls and the OLMoE decode call beside the three share shapes; seeded routing, microseconds a
call beside the least) with ``ops/grouped_gemm.py::_pick_tiles`` in four forms:

* ``parent``: the rule as it was before PR 36 (the largest ``tile_m`` dividing ``m``, then the widest
  ``tile_n`` of a fixed list whose working set WITH the backward's accumulator fits);
* ``tall``: the parent's ``tile_m`` (the largest dividing ``m``) with the committed rule's ``tile_n``
  for it (the forward's own working set): what the column tile alone gives;
* ``change``: the committed rule (``tile_m`` from the rows an expert holds);
* ``whole_n``: the committed ``tile_m`` with the whole N as ONE column tile wherever the blocks fit 28
  MiB, over ``_VMEM_BUDGET`` and so under a raised ``vmem_limit_bytes`` (ISSUE 36: Moonlight's gate / up
  N = 1408 = 11 x 128 has no lane-aligned divisor between 128 and itself).

Prints one JSON line a form; ``--rehearse`` prints each form's picks and runs nothing (no chip).
Nothing here is imported by the program.
"""

from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "tools"))


def _parent(m, k, n, groups=None, itemsize=2):
    for tm in (512, 256, 128):
        if m % tm:
            continue
        for tn in (1024, 896, 768, 640, 512, 384, 256, 128):
            if n % tn:
                continue
            need = 2 * 2 * (tm * k + k * tn + tm * tn) + 4 * max(tm * k, k * tn)
            if need <= 12 * 1024 * 1024:
                return tm, tn
    return 128, 128


def _forms():
    from deepspeed_tpu.ops import grouped_gemm as gg

    committed = gg._pick_tiles

    def widest(m, k, n, tm, budget):
        return next((tn for tn in range(n, 0, -128)
                     if n % tn == 0 and gg._forward_vmem(tm, k, tn) <= budget), 128)

    def tall(m, k, n, groups=None, itemsize=2):
        tm = next(t for t in (512, 256, 128) if m % t == 0)
        return tm, widest(m, k, n, tm, gg._VMEM_BUDGET)

    def whole_n(m, k, n, groups=None, itemsize=2):
        tm, _ = committed(m, k, n, groups)
        return tm, widest(m, k, n, tm, 28 << 20)

    return {"parent": _parent, "tall": tall, "change": committed, "whole_n": whole_n}


def main():
    import kernel_selftest as ks

    from deepspeed_tpu.ops import grouped_gemm as gg

    forms = _forms()
    names = [a for a in sys.argv[1:] if not a.startswith("--")] or list(forms)
    if "--rehearse" in sys.argv:
        for name in names:
            picks = {}
            for cell, (t, k_top, _, held, h, f) in ks.GMM_SHARE_CELLS.items():
                m = -(-t * k_top // 128) * 128
                picks[cell] = [forms[name](m, h, f, held), forms[name](m, f, h, held)]
            print(json.dumps({"form": name, "picks": picks}))
        return
    from deepspeed_tpu.utils.platform import require_tpu

    require_tpu("pr36_probe.py")
    committed = gg._pick_tiles
    for name in names:
        gg._pick_tiles = forms[name]
        try:
            print(json.dumps({"form": name, **ks.gmm_share_case(3e-2)}), flush=True)
        except Exception as e:  # a form the compiler refuses is a finding, not the probe's end
            print(json.dumps({"form": name, "error": f"{type(e).__name__}: {e}"[:600]}), flush=True)
        finally:
            gg._pick_tiles = committed


if __name__ == "__main__":
    main()
