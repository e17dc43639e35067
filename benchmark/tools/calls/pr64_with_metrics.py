"""PR 64: one run of a cell as ``benchmark/run.py`` makes it, with the five
set-up metrics this PR brings but cannot list (``BENCHMARK.json`` stands at
its cap of 128 per-layer entries: ``pr64_results/per_layer_proposed.json``
holds the five entries for the ``benchmark`` PR that makes room) appended to
the specification in memory.  A ``--trace 1`` line holds no end-to-end
metric, so the runner's own are logged as commentary (``end to end: ...``):
what a traced run costs is read from one ``--trace 1`` and one ``--trace 0``
run of this script.  Same arguments as ``run.py``:

    python3 benchmark/tools/calls/pr64_with_metrics.py --workload serve-mistral7b-chat-steady --seed <n> --seconds 51 --trace 1
"""
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

from benchmark import run                               # noqa: E402
from benchmark.lib import spec                          # noqa: E402

_PROPOSED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "pr64_results", "per_layer_proposed.json")


def main(argv=None) -> int:
    real_spec, real_module = spec.benchmark_spec, spec.module

    def with_proposed():
        bench = real_spec()
        have = {m["name"] for m in bench["per_layer"]}
        bench["per_layer"] += [m for m in spec.load_json(_PROPOSED)
                               if m["name"] not in have]
        return bench

    def module(kind, name):
        mod = real_module(kind, name)
        if kind == "runners" and not hasattr(mod, "_logs_end_to_end"):
            real_run = mod.run

            def run_and_log(ctx):
                res = real_run(ctx)
                ctx.log("end to end: " + ", ".join(
                    f"{k} {v}" for k, v in res["end_to_end"].items()))
                return res

            mod.run, mod._logs_end_to_end = run_and_log, True
        return mod

    spec.benchmark_spec, spec.module = with_proposed, module
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
