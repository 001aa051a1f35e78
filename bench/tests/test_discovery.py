"""Cells, configurations, traffic, limits and metric readers are found by
the names in BENCHMARK.json, so a new one is added by adding files."""
import json
import shutil
import types

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


# A family of its own: ViT's math under key names of its own, so that any
# ViT formula left in the harness would fail on its sizes file. Each
# function notes that it was called.
TOY_REFERENCE = '''
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "toy_vit", pathlib.Path(__file__).with_name("vit.py"))
_vit = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_vit)
CALLS = []
merge_schedule = _vit.merge_schedule


def _as_vit(c):
    return dict(c, patch_size=c["patch"], hidden_size=c["width"],
                mlp_dim=c["ffn"], num_heads=c["heads"])


def num_tokens(c):
    CALLS.append("num_tokens")
    return (c["image_size"] // c["patch"]) ** 2 + 1


def frame_flops(c, schedule):
    CALLS.append("frame_flops")
    return 1000.0 + sum(schedule)


def init_params(c, key, dtype):
    return _vit.init_params(_as_vit(c), key, dtype)


def logits(params, c, images, alpha, split, precision="f32"):
    return _vit.logits(params, _as_vit(c), images, alpha, split, precision)
'''
TOY_SERVED = '''
from repro.models import vit

CALLS = []


def model_config(c, dtype):
    CALLS.append("model_config")
    return vit.ViTConfig(img_res=c["image_size"], patch=c["patch"],
                         n_layers=c["num_layers"], d_model=c["width"],
                         n_heads=c["heads"], d_ff=c["ffn"],
                         n_classes=c["num_classes"], dtype=dtype)


def param_specs(model_cfg):
    CALLS.append("param_specs")
    return vit.specs(model_cfg)
'''
TOY_SIZES = {"family": "toy", "dtype": "float32", "image_size": 128, "num_layers": 2,
             "num_classes": 5, "tome": {"schedule": "exponential"},
             "patch": 16, "width": 32, "heads": 2, "ffn": 64}


@pytest.mark.parametrize("family", ["vit", "toy"])
def test_a_new_cell_config_and_metric_are_found_by_file_alone(tmp_path, monkeypatch, family):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCHMARK))
    # a configuration, a traffic mix, a cell, its limits and a per-layer
    # metric, each one new file plus its entry in BENCHMARK.json; a new
    # family is two more files
    if family == "vit":
        conf = json.loads((root / "bench/configs/vit-b16.json").read_text())
        conf["image_size"] = 384
    else:
        conf = TOY_SIZES
        (root / "bench/configs/toy.py").write_text(TOY_REFERENCE)
        (root / "bench/configs/toy_served.py").write_text(TOY_SERVED)
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
    assert cell.config == conf and cell.traffic["streams"] == 16
    # no "workloads" key: reported wherever its end-to-end metric is
    assert "frames_per_tick" in {m["name"] for m in cell.per_layer}
    assert "frames_per_tick" in {m["name"] for m in harness.load_cell(
        BENCHMARK["workloads"][0]["name"]).per_layer}
    run = harness.Run(ticks=[harness.Tick(0.01, 0.02)] * 4, frames=64, failed=0,
                      window_s=0.08, programs=70, batch_sizes=[8], max_batch=8,
                      chips=1, peak_flops=1.0)
    assert harness.reader("frames_per_tick").read(run) == 16
    if family == "toy":
        the_family_builds_and_counts(cell)


def the_family_builds_and_counts(cell):
    """``build`` takes the served config and the weights' layout from the
    family's adapter, ``tick_flops`` the tokens and operations from its
    reference."""
    fam, served = cell.family, cell.served
    sv = harness.build(cell, 2**31 + 5)
    assert served.CALLS == ["model_config", "param_specs"]
    assert sv.rt.model_cfg.d_model == 32 and sv.images[0].shape == (1, 128, 128, 3)
    fs = types.SimpleNamespace(all_frames=[types.SimpleNamespace(alpha=a)
                                           for a in (0.0, 0.5, 1.0)])
    want = sum(1000.0 + sum(fam.merge_schedule(a, 2, 65)) for a in (0.0, 0.5, 1.0))
    assert harness.tick_flops(cell, fs) == want > 3000.0
    assert "num_tokens" in fam.CALLS and fam.CALLS.count("frame_flops") == 3


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", BENCHMARK)
