"""PR 55, after a ``--trace 1`` run of the Qwen3-Next cell (run from a checkout's root): what the ``gdn/rule`` scope
of each jitted step program holds besides its two kernels.  Device ms an execution by instruction (HLO name and
shape), the executions counted by the events under the ``embed`` scope as ``scope_mixed.py`` counts them.

    python3 tools/chip_calls/pr55_rule_ops.py <cell> [scope substring, default gdn/rule]"""
import collections
import glob
import os
import re
import sys

sys.path.insert(0, os.getcwd())
from benchmark.lib import xplane_ops                    # noqa: E402
from benchmark.readers.scope_ms import scope_key        # noqa: E402

cell = sys.argv[1]
want = sys.argv[2] if len(sys.argv) > 2 else "gdn/rule"
found = glob.glob(f"bench_out/{cell}/trace/**/*.xplane.pb", recursive=True)
path = max(found, key=os.path.getmtime)
by = collections.defaultdict(lambda: collections.defaultdict(float))
total = collections.Counter()
runs = collections.Counter()
for _dev, s, e, op, text in xplane_ops.device_ops(path):
    m = re.match(r"jit\((\w+)\)", op or "")
    prog = m.group(1) if m else "(no op_name)"
    total[prog] += (e - s) / 1e6
    if op and scope_key(op.rstrip(":")) == "embed":
        runs[prog] += 1
    if op and want in op:
        by[prog][re.sub(r"\.\d+", "", text.split(" = ")[0])[:30] + " " + text.split(" = ")[-1][:60]] += (e - s) / 1e6
for prog, ops in sorted(by.items()):
    n = max(runs[prog], 1)
    tot = sum(ops.values())
    print(f"{prog}: {total[prog] / n:.2f} ms an execution ({runs[prog]} embed events), {want} {tot / n:.3f} ms:")
    for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:16]:
        print(f"    {v / n:.4f}  {k}")
