"""Decision-parity and caching tests for the table-driven vectorized
Algorithm-1 planner (``repro.core.planner``) against the legacy loop kept as
``scheduler._reference_schedule``, plus the compiled-plan cache on the
execution side."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from conftest import small_model_profile as _profile

import jax

from repro.core import bandwidth, engine, planner, pruning, scheduler
from repro.core.profiler import LinearProfiler
from repro.core.scheduler import ModelProfile
from repro.models import param as param_lib
from repro.models import vit as vit_lib


def _random_profile(pseed: int) -> ModelProfile:
    """A randomized-but-deterministic ModelProfile (layers, tokens, fitted
    slopes, embed/head constants, schedule kind all vary with ``pseed``)."""
    rng = np.random.default_rng(pseed)
    n = int(rng.integers(2, 33))
    x0 = int(rng.integers(40, 700))
    dev_a = 10 ** rng.uniform(-7, -4)
    dev_b = 10 ** rng.uniform(-5, -3)
    scale = rng.uniform(0.02, 0.9)  # cloud faster than device
    return ModelProfile(
        n_layers=n, x0=x0,
        token_bytes=float(rng.integers(64, 2048)),
        raw_input_bytes=float(rng.integers(10_000, 500_000)),
        device=LinearProfiler(dev_a, dev_b),
        cloud=LinearProfiler(dev_a * scale, dev_b * scale),
        device_embed_s=10 ** rng.uniform(-5, -3),
        cloud_embed_s=10 ** rng.uniform(-6, -4),
        head_s=10 ** rng.uniform(-6, -4),
        schedule_kind=["exponential", "linear"][int(rng.integers(2))])


def _assert_decisions_match(dec, ref):
    assert dec.alpha == ref.alpha
    assert dec.split == ref.split
    assert dec.meets_sla == ref.meets_sla
    assert tuple(dec.schedule) == tuple(ref.schedule)
    assert dec.predicted_latency_s == pytest.approx(ref.predicted_latency_s,
                                                    abs=1e-9)


# ---------------------------------------------------------------- parity

@given(pseed=st.integers(0, 10**6), bw=st.floats(1e4, 1e9),
       rtt=st.floats(0.0, 0.1), sla=st.floats(1e-4, 3.0))
@settings(max_examples=40, deadline=None)
def test_planner_matches_reference_on_random_profiles(pseed, bw, rtt, sla):
    """The vectorized planner returns a Decision identical to the legacy
    Algorithm-1 loop over randomized profiles, bandwidths, RTTs, and SLAs."""
    profile = _random_profile(pseed)
    ref = scheduler._reference_schedule(profile, bw, rtt, sla)
    dec = planner.tables_for(profile).decide(bw, rtt, sla)
    _assert_decisions_match(dec, ref)


def test_planner_matches_reference_on_fitted_profile():
    """Deterministic parity sweep on the fitted small profile across the
    feasible/fallback/device-only regimes."""
    p = _profile()
    for bw in (1e3, 1e5, 1e6, 5e6, 20e6, 80e6, 1e9):
        for sla in (1e-9, 0.05, 0.3, 10.0):
            ref = scheduler._reference_schedule(p, bw, 0.01, sla)
            dec = scheduler.schedule(p, bw, 0.01, sla)  # public API = tables
            _assert_decisions_match(dec, ref)


def test_schedule_respects_explicit_alpha_grid():
    p = _profile()
    grid = [0.0, 0.1, 0.2]
    ref = scheduler._reference_schedule(p, 2e6, 0.01, 1e-9, alpha_grid=grid)
    dec = scheduler.schedule(p, 2e6, 0.01, 1e-9, alpha_grid=grid)
    _assert_decisions_match(dec, ref)
    assert dec.alpha in grid


# ---------------------------------------------------------------- sweep_alpha

def test_sweep_alpha_meets_sla_honest():
    """The old sweep hardcoded meets_sla=False; it now reflects the SLA."""
    p = _profile()
    sla = 0.2
    out = scheduler.sweep_alpha(p, 20e6, 0.01, sla)
    assert len(out) == len(planner.tables_for(p).alpha_grid)
    for d in out:
        assert d.meets_sla == (d.predicted_latency_s <= sla)
    assert any(d.meets_sla for d in out) or all(not d.meets_sla for d in out)
    # default (no SLA constraint): every point trivially feasible, not False
    assert all(d.meets_sla for d in scheduler.sweep_alpha(p, 20e6, 0.01))


def test_sweep_alpha_matches_reference_per_alpha():
    """Per-α best (split, latency) agrees with the legacy loop run with a
    single-point α grid (no duplicated derivation drift)."""
    p = _profile()
    for bw in (1e5, 5e6, 80e6):
        for d in scheduler.sweep_alpha(p, bw, 0.01):
            ref = scheduler._reference_schedule(p, bw, 0.01, 1e-9,
                                                alpha_grid=[d.alpha])
            assert d.split == ref.split
            assert tuple(d.schedule) == tuple(ref.schedule)
            assert d.predicted_latency_s == pytest.approx(
                ref.predicted_latency_s, abs=1e-9)


# ---------------------------------------------------------------- tables cache

def test_tables_cached_by_profile_value():
    p1, p2 = _profile(), _profile()
    assert p1 is not p2
    assert planner.tables_for(p1) is planner.tables_for(p2), \
        "equal-valued profiles share one tables instance"
    assert planner.tables_for(p1, t=0.02) is not planner.tables_for(p1)


def test_engines_share_tables_and_fixed_baseline_cached():
    p = _profile()
    cfg = engine.EngineConfig(sla_s=0.3)
    e1, e2 = engine.JanusEngine(p, cfg), engine.JanusEngine(p, cfg)
    assert e1.tables is e2.tables
    # fixed baseline schedule/counts derived once per engine, not per frame
    d1 = e1._decide("device", 1e6, 0.01)
    d2 = e1._decide("device", 2e6, 0.01)
    assert d1.schedule is d2.schedule is e1._fixed_schedule
    expected = tuple(pruning.clamp_schedule(
        pruning.fixed_schedule(cfg.baseline_fixed_r, p.n_layers), p.x0))
    assert e1._fixed_schedule == expected
    # device-only latency is bandwidth-independent
    assert d1.predicted_latency_s == d2.predicted_latency_s


def test_counts_row_and_payload_table_consistent():
    p = _profile()
    tab = planner.tables_for(p)
    n = p.n_layers
    for i, alpha in enumerate(tab.alpha_grid):
        counts = pruning.token_counts(p.x0, tab.schedules[i])
        np.testing.assert_array_equal(tab.counts_row(float(alpha)), counts)
        for j, s in enumerate(tab.candidates):
            s = int(s)
            expected = 0.0 if s in (0, n + 1) else counts[s] * p.token_bytes
            assert tab.payload[i, j] == expected
    with pytest.raises(KeyError):
        tab.alpha_index(0.123456)


def test_account_breakdown_matches_decision_prediction():
    """At the estimated bandwidth, account_breakdown of the chosen (α, split)
    reproduces the planner's predicted E2E latency."""
    p = _profile()
    eng = engine.JanusEngine(p, engine.EngineConfig(sla_s=0.3))
    for bw in (1e5, 5e6, 80e6):
        dec = eng.tables.decide(bw, 0.01, 0.3)
        counts = eng._counts_for(dec.schedule)
        payload = eng._payload_bytes(counts, dec.split)
        bd = eng.account_breakdown(counts, dec.split, payload, bw, 0.01)
        assert bd.total_s == pytest.approx(dec.predicted_latency_s, rel=1e-9)


def test_legacy_planner_config_uses_reference_loop():
    p = _profile()
    trace = bandwidth.NetworkTrace(np.full(6, 5e6), 0.01, "steady")
    cfg = dict(sla_s=0.3, include_scheduler_overhead=False)
    st_tab = engine.JanusEngine(
        p, engine.EngineConfig(**cfg)).run_trace(trace, 6, "janus")
    st_leg = engine.JanusEngine(
        p, engine.EngineConfig(**cfg, planner="legacy")).run_trace(trace, 6, "janus")
    assert [f.split for f in st_tab.frames] == [f.split for f in st_leg.frames]
    assert [f.alpha for f in st_tab.frames] == [f.alpha for f in st_leg.frames]
    np.testing.assert_allclose([f.latency_s for f in st_tab.frames],
                               [f.latency_s for f in st_leg.frames])


# ---------------------------------------------------------------- plan cache

def _exec_engine(**cfg_kw):
    cfg = vit_lib.ViTConfig(img_res=32, patch=8, n_layers=4, d_model=32,
                            n_heads=2, d_ff=64, n_classes=8)
    params = param_lib.init_params(vit_lib.specs(cfg), jax.random.key(0))
    images = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    eng = engine.JanusEngine(
        _profile(),
        engine.EngineConfig(sla_s=0.5, execute=True,
                            include_scheduler_overhead=False, **cfg_kw),
        model_cfg=cfg, params=params)
    return eng, images


def test_compiled_plan_cache_no_retrace_on_repeat_geometry():
    """Second frame with the same (schedule, split, shape) must hit the cache:
    the trace counter (bumped only while jax traces) stays flat."""
    eng, images = _exec_engine()
    trace = bandwidth.NetworkTrace(np.full(4, 80e6), 0.002, "steady")
    est = bandwidth.HarmonicMeanEstimator(cold_start_bps=80e6)

    step0 = eng.plan_frame(0, trace, "janus", est, images=images)
    est.observe(step0.bandwidth_bps)
    # cloud-only at this bandwidth: no device program, one cloud program
    # that embeds the frame itself
    assert step0.decision.split == 0, "geometry precondition drifted"
    traces_after_first = eng.plan_cache.traces
    assert eng.plan_cache.traces_by_kind == {"cloud_embed": 1}, \
        "the cloud-only partition traced once"
    assert eng.plan_cache.misses == 1 and eng.plan_cache.hits == 0

    for i in (1, 2, 3):
        step = eng.plan_frame(i, trace, "janus", est, images=images)
        est.observe(step.bandwidth_bps)
        assert step.decision.split == step0.decision.split
    assert eng.plan_cache.traces == traces_after_first, "retraced on repeat"
    assert eng.plan_cache.misses == 1
    assert eng.plan_cache.hits == 3
    assert step.exec_plan.logits is not None


def test_run_trace_execute_produces_logits_matching_split_inference():
    eng, images = _exec_engine(quantize_payload=False)
    trace = bandwidth.NetworkTrace(np.full(3, 80e6), 0.002, "steady")
    st = eng.run_trace(trace, 3, "janus", images=images)
    cfg, n_exec = eng.model_cfg, eng.model_cfg.n_layers
    for f in st.frames:
        assert f.logits is not None and f.logits.shape == (1, cfg.n_classes)
        sched = tuple(pruning.make_schedule(eng.profile.schedule_kind, f.alpha,
                                            n_exec, cfg.num_tokens))
        split_exec = n_exec + 1 if f.split >= eng.profile.n_layers + 1 else \
            min(round(f.split * n_exec / eng.profile.n_layers), n_exec)
        expected, _ = engine.split_inference(eng.params, cfg, images, sched,
                                             split_exec, quantize=False)
        np.testing.assert_allclose(np.asarray(f.logits), np.asarray(expected),
                                   atol=1e-5, rtol=1e-5)
