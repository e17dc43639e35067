"""PR 60: one run of a cell as ``benchmark/run.py`` makes it, with the
per-layer metric this PR brings but cannot list (``BENCHMARK.json`` stands
at its cap of 128 per-layer entries; ``pr60_results/per_layer_proposed.json``
holds the entry, and ``benchmark/layer_metrics/moe_combine_ms_tick.json`` is
its data, for the ``benchmark`` PR that makes room) appended to the
specification in memory: ``benchmark/tools/calls/pr59_with_metrics.py`` on
this PR's file.  ``CHECKOUT=<dir>`` runs another tree (the parent, with the
data file laid over it).  Same arguments as ``run.py``:

    python3 tools/chip_calls/pr60_with_metrics.py --workload serve-qwen3next-longchat-closed32 --seed <n> --seconds 51 --trace 1
"""
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.abspath(os.environ.get(
    "CHECKOUT", os.path.dirname(os.path.dirname(_HERE)))))

from benchmark.tools.calls import pr59_with_metrics     # noqa: E402

pr59_with_metrics._PROPOSED = os.path.join(
    _HERE, "pr60_results", "per_layer_proposed.json")

if __name__ == "__main__":
    sys.exit(pr59_with_metrics.main())
