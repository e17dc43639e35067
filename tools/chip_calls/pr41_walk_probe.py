"""PR 41, chip call 1: the decode walk on every cell's pool IN THE FORM THE
TREE IT RUNS IN STORES IT, before the new form is trusted end to end.

    python3 tools/chip_calls/pr41_walk_probe.py <checkout> <out.json> [step=N] [cell ...]

``step=N`` multiplies the table entries a step of the walk copies
(``_walk_step_blocks``) by N, for this probe alone; ``shares=a,b`` replaces
the shares of the pool the rows hold (the chat cell holds 12%: four or five
live rows, the rest pads).

``<checkout>`` is the tree whose ``deepspeed_tpu`` is measured (the parent
under ``build/parent``: ``[rows, Hkv, D]`` and the masked product over every
KV head of a step; the change: the flat row ``[rows, Hkv*D]`` and a dot a KV
head).  The case is THIS tree's ``tools/kernel_selftest.py::decode_read_case``
on both sides: it asks ``BlockedKVCache`` of the checkout for the stored row,
so each side walks what its engine would hand it.  Writes ``{cell: {max_err,
ok, us: {share: [blocks held, walk us, dense read us, least us]}}}``."""
import importlib.util
import json
import os
import sys

root, out = os.path.abspath(sys.argv[1]), sys.argv[2]
cells = [a for a in sys.argv[3:] if "=" not in a]
step = [int(a.split("=")[1]) for a in sys.argv[3:] if a.startswith("step=")]
shares = [tuple(float(x) for x in a.split("=")[1].split(","))
          for a in sys.argv[3:] if a.startswith("shares=")]
sys.path.insert(0, root)
here = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
spec = importlib.util.spec_from_file_location(
    "pr41_kernel_selftest", os.path.join(here, "tools", "kernel_selftest.py"))
ks = importlib.util.module_from_spec(spec)
spec.loader.exec_module(ks)

import deepspeed_tpu                                        # noqa: E402

assert os.path.abspath(deepspeed_tpu.__file__).startswith(root), \
    deepspeed_tpu.__file__
if step:
    from deepspeed_tpu.inference.v2.kernels import blocked_flash

    real = blocked_flash._walk_step_blocks
    blocked_flash._walk_step_blocks = lambda b, width, quantized: max(
        1, min(real(b, 1 << 30, quantized) * step[0], width))
results = {}
for cell in cells or list(ks.DECODE_READ_CELLS):
    nb, hkv, g, d = ks.DECODE_READ_CELLS[cell]
    row = tuple(ks._stored_row(hkv, d))
    try:
        results[cell] = dict(ks.decode_read_case(
            cell, 3e-2, **({"shares": shares[0]} if shares else {})), row=row)
    except Exception as e:  # noqa: BLE001  (one cell must not erase the rest)
        results[cell] = {"ok": False, "row": row,
                         "error": str(e).splitlines()[0][:300]}
    print(cell, json.dumps(results[cell]), flush=True)
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
with open(out, "w") as f:
    json.dump(results, f, indent=1)
