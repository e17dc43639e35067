"""PR 41, chip call 10: the speculative verify read on a Mistral-shaped pool IN THE FORM THE TREE IT RUNS IN STORES IT.

    python3 tools/chip_calls/pr41_verify_probe.py <checkout> <out.json>

``<checkout>`` is the tree whose ``deepspeed_tpu`` is measured (the parent under ``build/parent``: ``[rows, Hkv, D]`` and
``_verify_kernel`` on ``[bs, Hkv, D]`` blocks; the change: the flat row and the same kernel on ``[bs, Hkv*D]`` blocks, a
KV head a static lane slice).  The case is THIS tree's ``tools/kernel_selftest.py::verify_read_case`` on both sides: it asks
``BlockedKVCache`` of the checkout for the stored row.  Writes ``{max_err, ok, row, us: [blocks held, verify us, least
us]}``."""
import importlib.util
import json
import os
import sys

root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location("pr41_kernel_selftest", os.path.join(here, "tools", "kernel_selftest.py"))
ks = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ks)

import deepspeed_tpu                                        # noqa: E402

assert os.path.abspath(deepspeed_tpu.__file__).startswith(root), deepspeed_tpu.__file__
result = ks.verify_read_case(3e-2)
print("verify", root, json.dumps(result), flush=True)
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
with open(out, "w") as f:
    json.dump(result, f, indent=1)
