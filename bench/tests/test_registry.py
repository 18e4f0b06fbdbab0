"""The registry finds every piece of a cell by its name, and a new
configuration, traffic mix, metric or work count is added by adding files
and entries alone."""

import json
import shutil

import pytest

import registry


def test_every_cell_resolves():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        cell = registry.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["mode"] in ("whole_track", "per_observation")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        registry.load_module("reference", cell.config["reference"])
        for entry in ("kernel", "step"):
            assert registry.work(cell.config, entry)["bytes"] > 0


def test_every_metric_has_a_reader():
    bench = registry.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.load_module("metrics", m["name"]).read)


def test_config_files_lie_under_paths_and_name_their_keys():
    bench = registry.load_benchmark()
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        cfg = json.loads((registry.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_a_new_cell_is_files_and_entries(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(registry.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = registry.load_benchmark()
    cfg = json.loads((registry.ROOT / bench["configs"][0]["file"]).read_text())
    cfg.update(name="ungm-alg6-n2e14-b32", num_particles=1 << 14,
               counts={"kernel": "toy_count", "step": "toy_count"})
    (root / "bench/configs/ungm-alg6-n2e14-b32.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        {"mode": "per_observation", "steps_per_track": 10, "pool": 2,
         "warmup_steps": 1, "check_tracks": 1}))
    (root / "bench/metrics/toy_metric.py").write_text("def read(ctx):\n    return 1.5\n")
    (root / "bench/counts/toy_count.py").write_text(
        "def count(cfg):\n    return {'bytes': cfg['num_particles'], 'ops': 0}\n")
    bench["configs"].append({"name": cfg["name"], "source": "x",
                             "file": "bench/configs/ungm-alg6-n2e14-b32.json",
                             "reduced": ["num_particles"], "why": "x"})
    bench["workloads"].append({"name": "ungm-alg6-n2e14-b32.burst", "config": cfg["name"],
                               "traffic": "burst", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "toy_metric", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "x", "moves": "step_ms",
                               "workloads": ["ungm-alg6-n2e14-b32.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = registry.find_cell("ungm-alg6-n2e14-b32.burst", root)
    assert cell.config["num_particles"] == 1 << 14 and cell.traffic["steps_per_track"] == 10
    assert [m["name"] for m in cell.per_layer][-1] == "toy_metric"
    assert registry.load_module("metrics", "toy_metric", root).read(None) == 1.5
    assert registry.load_module("counts", "toy_count", root).count(cell.config)["bytes"] == 1 << 14
    # a metric limited to other cells is not reported here
    other = registry.find_cell(bench["workloads"][0]["name"], root)
    assert "toy_metric" not in [m["name"] for m in other.per_layer]


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        registry.find_cell("no-such.cell")
