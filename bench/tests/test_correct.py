"""The check that decides ``correct``, driven through a whole run at a size
a CPU holds: a narrow ViT of 65 tokens and 4 layers, served through the
real fleet path (planner, device partitions, bucketed cloud micro-batches
with ToMe merges), held to the limits of the cell it stands in for.

The planner prices a model 64x as wide as the one executed, so that it
sends frames to the cloud pruned, as it does at the cell's own size."""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

import harness
from compile_counter import CompileCounter
from repro.launch import serve
from repro.serving import simcore

CELL = "vit-l384.4g-pruned"
SEED = 2**31 + 77
TINY = dict(image_size=128, patch_size=16, hidden_size=64, mlp_dim=128,
            num_heads=4, num_layers=4, num_classes=10)


@pytest.fixture
def cell(monkeypatch):
    wide = serve.make_profile
    monkeypatch.setattr(serve, "make_profile", lambda cfg: wide(
        dataclasses.replace(cfg, d_model=4096, d_ff=16384)))
    c = harness.load_cell(CELL)
    c.config.update(TINY)
    c.traffic.update(streams=8, sla_ms=30)
    return c


def run(cell):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                            CompileCounter(), "TPU v5 lite", 1)


def test_a_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"frames_per_s", "tick_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_pruned_frames_are_checked(cell, monkeypatch):
    seen = []
    real = harness.compare

    def spy(cell, params, images, frames, **kw):
        seen.extend((a, s) for _, a, s, _ in frames)
        return real(cell, params, images, frames, **kw)

    monkeypatch.setattr(harness, "compare", spy)
    run(cell)
    assert any(a > 0 and s == 0 for a, s in seen), set(seen)


def test_the_control_is_not_correct(cell, monkeypatch):
    """The reference in the control's precision, put in the served path's
    place."""
    real = harness.served_frames

    def control(fs, image_index):
        frames = real(fs, image_index)
        params, images = fs_state["params"], fs_state["images"]
        out = []
        for i, a, s, _ in frames:
            lg = cell.family.logits(params, cell.config, images[i], a, s,
                                    cell.limits["control"])
            out.append((i, a, s, lg))
        return out

    fs_state = {}
    real_build = harness.build

    def build(c, seed):
        sv = real_build(c, seed)
        fs_state.update(params=sv.params, images=sv.images)
        return sv

    monkeypatch.setattr(harness, "build", build)
    monkeypatch.setattr(harness, "served_frames", control)
    assert not run(cell)["correct"]


def _broken(monkeypatch, fault):
    real = simcore.run_cloud_batch

    def broken(cache, cfg, params, plans, buckets=None):
        plans = [p for p in plans if p is not None]
        fault(real, cache, cfg, params, plans, buckets)

    monkeypatch.setattr(simcore, "run_cloud_batch", broken)


def test_half_of_each_batch_left_out_is_not_correct(cell, monkeypatch):
    def half(real, cache, cfg, params, plans, buckets):
        real(cache, cfg, params, plans[:max(1, len(plans) // 2)], buckets=buckets)

    _broken(monkeypatch, half)
    out = run(cell)
    assert not out["correct"] and out["checks"]["missing"]["value"] > 0


def test_an_altered_answer_is_not_correct(cell, monkeypatch):
    def altered(real, cache, cfg, params, plans, buckets):
        real(cache, cfg, params, plans, buckets=buckets)
        plans[0].logits = jnp.roll(plans[0].logits, 1, axis=-1)

    _broken(monkeypatch, altered)
    assert not run(cell)["correct"]


def test_compare_reads_zero_against_itself(cell):
    import jax
    c = cell.config
    params = cell.family.init_params(c, jax.random.key(0), jnp.bfloat16)
    images = (jax.random.normal(jax.random.key(1), (1, 128, 128, 3)),)
    ref = np.asarray(cell.family.logits(params, c, images[0], 0.5, 0))
    got = harness.compare(cell, params, images, [(0, 0.5, 0, ref)])
    assert got["rel_l2"] < 1e-6 and got["missing"] == got["nonfinite"] == 0
