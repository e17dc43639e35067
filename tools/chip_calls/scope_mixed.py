"""One-off of PR 24 (ran as build/scope_mixed.py, from a checkout's root, after a
``--trace 1`` run): device ms by scope of each jitted step program in the newest trace
under bench_out/<cell>/trace.  "Executions" counts the events under the ``embed`` scope,
which is two a ``put`` program and three a ``decode_step``: normalise by
``attn/dense_read`` (2.585 ms an execution of any program of the serving cells).

    python3 tools/chip_calls/scope_mixed.py <cell>
"""
import collections
import glob
import os
import re
import sys

sys.path.insert(0, os.getcwd())
from benchmark.lib import xplane_ops                    # noqa: E402
from benchmark.readers.scope_ms import scope_key        # noqa: E402

cell = sys.argv[1]
found = glob.glob(f"bench_out/{cell}/trace/**/*.xplane.pb", recursive=True)
path = max(found, key=os.path.getmtime)
by = collections.defaultdict(lambda: collections.defaultdict(float))
runs = collections.Counter()
for _dev, s, e, op, text in xplane_ops.device_ops(path):
    m = re.match(r"jit\((\w+)\)", op or "")
    prog = m.group(1) if m else "(no op_name)"
    key = scope_key(op.rstrip(":")) if op else text.split(" ")[0][:40]
    by[prog][key] += (e - s) / 1e6
    if key == "embed":
        runs[prog] += 1
for prog, scopes in sorted(by.items()):
    tot = sum(scopes.values())
    n = max(runs[prog], 1)
    top = sorted(scopes.items(), key=lambda kv: -kv[1])[:14]
    print(f"{prog}: {tot:.1f} ms in the stretch, ~{runs[prog]} executions (events under "
          f"the embed scope), per execution {tot / n:.2f} ms: "
          + ", ".join(f"{k} {v / n:.2f}" for k, v in top))
