"""BENCHMARK.json and the files it names agree, and a new cell / config /
mix / metric / reader needs only new files and entries."""

import importlib
import json
import os
import shutil

import pytest

from benchmark.lib import spec


def test_every_name_has_its_file():
    b = spec.benchmark_spec()
    for c in b["configs"]:
        cfg = spec.load_json(os.path.join(spec.CHECKOUT, c["file"]))
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        spec.module("runners", cfg["runner"])
        fam = spec.module("families", cfg["family"])
        spec.module("reference", fam.REFERENCE)
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        spec.traffic_for(w)
        spec.config_for(b, w)
        names = {m["name"] for m in spec.metrics_for(b, "end_to_end",
                                                     w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_for(b, "per_layer", w["name"])
    for m in b["per_layer"]:
        how = spec.layer_metric_file(m["name"])
        # layer, unit and moves live in BENCHMARK.json alone
        assert set(how) <= {"what", "reader", "args"}
        assert m["moves"] in e2e
        for w in m.get("workloads", [x["name"] for x in b["workloads"]]):
            assert m["moves"] in {x["name"] for x in spec.metrics_for(
                b, "end_to_end", w)}, (m["name"], w)
        assert hasattr(spec.module("readers", how["reader"]), "read")


def test_four_chip_cells_are_at_most_a_quarter():
    b = spec.benchmark_spec()
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)


def test_adding_a_cell_needs_only_new_files(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    b = spec.benchmark_spec()
    # a new mix, a new configuration, a new per-layer metric with a new
    # reader: four new files ...
    (root / "benchmark/traffic/chat-fast.json").write_text(json.dumps(
        {"kind": "open_loop", "stratify_block": 8,
         "arrivals": {"rate_per_s": 3.0},
         "prompt_tokens": {"dist": "uniform", "min": 64, "max": 64},
         "output_tokens": {"dist": "uniform", "min": 16, "max": 16}}))
    cfg = spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "mistral-7b-v0.1-serve-1chip.json"))
    cfg["num_hidden_layers"] = 8
    (root / "benchmark/configs/mistral-half.json").write_text(json.dumps(cfg))
    (root / "benchmark/layer_metrics/ttft_p99_ms.json").write_text(json.dumps(
        {"reader": "my_reader", "args": {"q": 99}}))
    (root / "benchmark/readers/my_reader.py").write_text(
        "def read(facts, args, ctx):\n    return 1.0 * args['q']\n")
    # ... and entries in BENCHMARK.json; no existing file is edited
    b["configs"].append({"name": "mistral-half", "source": cfg["source"],
                         "file": "benchmark/configs/mistral-half.json",
                         "reduced": ["num_hidden_layers"], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "mistral-half",
                           "traffic": "chat-fast", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "ttft_p99_ms", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "client view", "moves": "itl_p50_ms",
                           "workloads": ["new-cell"]})
    for m in b["end_to_end"]:
        if "workloads" in m and m["name"] == "itl_p50_ms":
            m["workloads"].append("new-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(spec, "CHECKOUT", str(root))
    monkeypatch.setattr(spec, "BENCH_DIR", str(root / "benchmark"))
    monkeypatch.syspath_prepend(str(root))
    for name in [n for n in list(importlib.sys.modules)
                 if n == "benchmark" or n.startswith("benchmark.readers")]:
        monkeypatch.delitem(importlib.sys.modules, name)
    import benchmark
    monkeypatch.setattr(benchmark, "__path__", [str(root / "benchmark")])

    nb = spec.benchmark_spec()
    cell = spec.cell(nb, "new-cell")
    assert spec.config_for(nb, cell)["num_hidden_layers"] == 8
    assert spec.traffic_for(cell)["arrivals"]["rate_per_s"] == 3.0
    mine = [m for m in spec.metrics_for(nb, "per_layer", "new-cell")
            if m["name"] == "ttft_p99_ms"]
    assert mine
    how = spec.layer_metric_file("ttft_p99_ms")
    assert spec.module("readers", how["reader"]).read({}, how["args"],
                                                      None) == 99.0
    with pytest.raises(spec.SpecError):
        spec.cell(nb, "no-such-cell")
