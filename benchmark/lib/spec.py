"""Finding things by name.  ``BENCHMARK.json`` names cells, configurations,
traffic mixes and metrics; everything that belongs to one of them lives in
a file of its own under ``benchmark/`` that is found from that name, so a
later PR adds a cell by adding files and entries, never by editing one."""

from __future__ import annotations

import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    """The benchmark's own files contradict each other or are missing."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec() -> Dict[str, Any]:
    return load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json; have "
                    f"{[e['name'] for e in entries]}")


def cell(spec: dict, workload: str) -> dict:
    return _by_name(spec["workloads"], workload, "workload")


def config_for(spec: dict, cell_entry: dict) -> dict:
    entry = _by_name(spec["configs"], cell_entry["config"], "config")
    cfg = load_json(os.path.join(CHECKOUT, entry["file"]))
    cfg["name"] = entry["name"]
    return cfg


def traffic_for(cell_entry: dict) -> dict:
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 cell_entry["traffic"] + ".json"))
    mix["name"] = cell_entry["traffic"]
    return mix


def metrics_for(spec: dict, group: str, workload: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    with no ``workloads`` key, or with the cell in it."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def layer_metric_file(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "layer_metrics", name + ".json"))


def module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` (kind: runners, readers, families,
    reference)."""
    return importlib.import_module(f"benchmark.{kind}.{name}")
