"""``python -m pytest benchmark/tests -q`` — run by hand, on the CPU; not part
of the repository's tier-1 tests.  Four virtual CPU devices, so the
ZeRO-3 x TP runner rehearses on a 2 x 2 mesh."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _CHECKOUT not in sys.path:
    sys.path.insert(0, _CHECKOUT)
