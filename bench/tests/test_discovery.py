"""Cells, configurations, traffic, limits and metric readers are found by
the names in BENCHMARK.json, so a new one is added by adding files."""
import json
import shutil

import pytest

import harness

BENCHMARK = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in BENCHMARK["workloads"]])
def test_every_cell_resolves(workload):
    cell = harness.load_cell(workload, BENCHMARK)
    assert cell.config["family"] and cell.traffic["streams"] > 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert set(cell.limits) >= {"missing", "nonfinite"}
    for m in cell.per_layer:
        assert callable(harness.reader(m["name"]).read)
    fam = cell.family
    assert callable(fam.logits) and callable(fam.init_params)


def test_a_new_cell_config_and_metric_are_found_by_file_alone(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCHMARK))
    # a configuration, a traffic mix, a cell, its limits and a per-layer
    # metric, each one new file plus its entry in BENCHMARK.json
    conf = json.loads((root / "bench/configs/vit-b16.json").read_text())
    conf["image_size"] = 384
    (root / "bench/configs/vit-b16-384.json").write_text(json.dumps(conf))
    traffic = json.loads((root / "bench/traffic/wifi-static-64.json").read_text())
    traffic["streams"] = 16
    (root / "bench/traffic/wifi-static-16.json").write_text(json.dumps(traffic))
    (root / "bench/limits/vit-b16-384.wifi-16.json").write_text(
        json.dumps({"missing": 0, "nonfinite": 0}))
    (root / "bench/metrics/frames_per_tick.py").write_text(
        "def read(run):\n    return run.frames / len(run.ticks)\n")
    bench["configs"].append({"name": "vit-b16-384", "source": "x",
                             "file": "bench/configs/vit-b16-384.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "vit-b16-384.wifi-16", "config": "vit-b16-384",
                               "traffic": "wifi-static-16", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "frames_per_tick", "unit": "frames",
                               "better": "higher", "source": "program_counter",
                               "layer": "host path", "moves": "frames_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")

    cell = harness.load_cell("vit-b16-384.wifi-16")
    assert cell.config["image_size"] == 384 and cell.traffic["streams"] == 16
    # no "workloads" key: reported wherever its end-to-end metric is
    assert "frames_per_tick" in {m["name"] for m in cell.per_layer}
    assert "frames_per_tick" in {m["name"] for m in harness.load_cell(
        BENCHMARK["workloads"][0]["name"]).per_layer}
    run = harness.Run(ticks=[harness.Tick(0.01, 0.02)] * 4, frames=64, failed=0,
                      window_s=0.08, programs=70, batch_sizes=[8], max_batch=8,
                      chips=1, peak_flops=1.0)
    assert harness.reader("frames_per_tick").read(run) == 16


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", BENCHMARK)
