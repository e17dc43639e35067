"""PR 61: one run of a cell as ``benchmark/run.py`` makes it, with the four
per-layer metrics this PR brings but cannot list (``BENCHMARK.json`` stands
at its cap of 128 per-layer entries: ``pr61_results/per_layer_proposed.json``
holds the four entries for the ``benchmark`` PR that makes room) appended
to the specification in memory.  Same arguments as ``run.py``:

    python3 benchmark/tools/calls/pr61_with_metrics.py --workload serve-dots3-notes-closed48 --seed <n> --seconds 51 --trace 1
"""
import os
import sys

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, _CHECKOUT)

from benchmark import run                               # noqa: E402
from benchmark.lib import spec                          # noqa: E402

_PROPOSED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "pr61_results", "per_layer_proposed.json")


def main(argv=None) -> int:
    real = spec.benchmark_spec

    def with_proposed():
        bench = real()
        have = {m["name"] for m in bench["per_layer"]}
        bench["per_layer"] += [m for m in spec.load_json(_PROPOSED)
                               if m["name"] not in have]
        return bench

    spec.benchmark_spec = with_proposed
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
