"""PR 46, calls 1-2: both selective-scan kernels alone at the cell's shapes
against their XLA compositions (``tools/kernel_selftest.py::ssm_case``),
microseconds a call beside the least time their bytes need."""
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "tools")]

import kernel_selftest                                  # noqa: E402

out = {}
for which in ("step", "chunk"):
    try:
        out[which] = kernel_selftest.ssm_case(which)
    except Exception as e:  # noqa: BLE001
        out[which] = {"error": str(e)[:2000]}
print(json.dumps(out, indent=1))
