"""PR 59: the Granite-4.0-H cell's step programs at real size for a
described v5e, no chip: ``pr56_aot.py`` (an engine with state slots beside
its KV pool) on this PR's configuration, counting this model's kernels.
What it is for: whether ``_ssd_step_kernel`` and ``_ssd_chunk_kernel`` lower
at 128 heads of 64 x 128 states, whether the grouped GEMM does at 18 experts
768 wide, and whether 5.91 GB of weights + 4.93 GB of state slots + the KV
pool + the largest program's temporaries stay under the chip's 16 GB.

    JAX_PLATFORMS=cpu python3 benchmark/tools/calls/pr59_aot.py [key=value ...] [rows ...]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import pr56_aot                                         # noqa: E402

pr56_aot.KERNELS = ("_ssd_step_kernel", "_ssd_chunk_kernel", "_gmm_kernel",
                    "_decode_kernel", "_prefill_kernel")

if __name__ == "__main__":
    pr56_aot.main("granite-4.0-h-small-serve-1chip", *sys.argv[1:])
