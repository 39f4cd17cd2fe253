"""A configuration, a traffic mix and a per-layer metric are added by
adding files and manifest entries alone."""
import json
import os
import shutil

import _tiny_cells

from bench import manifest


def test_loader_finds_pieces_that_exist_only_as_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(_tiny_cells.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_tiny_cells.ROOT, "BENCHMARK.json"), root)
    man = json.loads((root / "BENCHMARK.json").read_text())

    conf = json.loads((root / "bench/configs/phi4-mini-3.8b-serve.json").read_text())
    conf["name"] = "new-model"
    (root / "bench/configs/new-model.json").write_text(json.dumps(conf))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"kind": "requests", "arrivals": "backlog", "count": 3,
         "prompt": {"median": 8, "sigma": 0.1, "min": 4, "max": 12},
         "output": {"median": 4, "sigma": 0.1, "min": 2, "max": 6},
         "check_tokens": 4}))
    (root / "bench/metrics/new_metric.x.py").write_text(
        "def read(run):\n    return run.get('answer')\n")
    (root / "bench/check/limits/new-cell.json").write_text(
        json.dumps({"limits": {"logit_gap": {"limit": 1.0}}}))
    man["configs"].append({"name": "new-model", "source": "https://example.org/m",
                           "file": "bench/configs/new-model.json", "reduced": [],
                           "why": "added by files alone"})
    man["workloads"].append({"name": "new-cell", "config": "new-model",
                             "traffic": "new-mix", "chips": 1, "why": "test"})
    man["end_to_end"].append({"name": "new_e2e", "unit": "ms", "better": "lower",
                              "bound": 0.1, "source": "host_clock",
                              "workloads": ["new-cell"]})
    man["per_layer"].append({"name": "new_metric.x", "unit": "%", "better": "higher",
                             "source": "program_counter", "layer": "test",
                             "moves": "new_e2e", "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    cell = manifest.load_cell("new-cell", root=str(root))
    assert cell.config["name"] == "new-model"
    assert cell.traffic["count"] == 3
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert "new_e2e" in [m["name"] for m in cell.end_to_end]
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert cell.metric_reader("new_metric.x").read({"answer": 42}) == 42
    assert cell.driver().__name__ == "bench_driver_serve"
    assert hasattr(cell.family(), "forward") or hasattr(cell.family(), "hidden")


def test_every_cell_of_the_manifest_resolves():
    man = json.load(open(os.path.join(_tiny_cells.ROOT, "BENCHMARK.json")))
    for w in man["workloads"]:
        cell = manifest.load_cell(w["name"])
        for m in cell.per_layer:
            assert hasattr(cell.metric_reader(m["name"]), "read")
            assert m["moves"] in [e["name"] for e in cell.end_to_end]
        assert cell.limits["limits"]
