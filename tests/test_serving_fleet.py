"""Regression tests for the batcher timing fixes + the multi-stream fleet
runtime (stream isolation, cloud saturation, N=1 equivalence with the
single-stream engine, batched real-math cloud execution)."""
import jax
import numpy as np
import pytest
from conftest import small_model_profile as _profile

from repro.core import bandwidth, engine, pruning
from repro.models import param as param_lib
from repro.models import vit as vit_lib
from repro.serving import fleet
from repro.serving.batcher import ContinuousBatcher, MicroBatcher, Request


# ------------------------------------------------- MicroBatcher.poll (expiry)

def test_microbatcher_poll_expires_stale_batch():
    """A pending batch must flush via poll() even when no new frame ever
    arrives (the low-load staleness bug)."""
    mb = MicroBatcher(max_batch=8, max_wait_s=0.01)
    assert mb.offer(Request(0, arrival_s=1.0), now=1.0) is None
    assert mb.deadline() == pytest.approx(1.01)
    assert mb.poll(1.005) is None, "deadline not reached yet"
    out = mb.poll(1.01)
    assert out is not None and [r.rid for r in out] == [0]
    assert mb.deadline() is None and mb.poll(2.0) is None


def test_microbatcher_poll_exact_deadline_no_float_stranding():
    """poll() at exactly deadline() must flush: ``now - arrival >= wait`` can
    round below ``wait`` and strand the batch forever (seen with arrival
    ~22.61 and wait 5ms)."""
    arrival, wait = 22.6100513286731, 0.005
    mb = MicroBatcher(max_batch=4, max_wait_s=wait)
    assert mb.offer(Request(0, arrival_s=arrival), now=arrival) is None
    assert mb.poll(mb.deadline()) is not None


def test_microbatcher_offer_still_flushes_on_size():
    mb = MicroBatcher(max_batch=2, max_wait_s=10.0)
    assert mb.offer(Request(0, arrival_s=0.0), now=0.0) is None
    out = mb.offer(Request(1, arrival_s=0.1), now=0.1)
    assert out is not None and len(out) == 2


# ----------------------------------- ContinuousBatcher idle-gap clock jumping

def test_continuous_batcher_idle_gap_not_billed_as_decode_steps():
    """A request arriving at t=5 must not cost five idle decode steps: the
    clock jumps to the arrival and exactly ``max_new`` steps are billed."""
    calls = []

    def step_time(n):
        calls.append(n)
        return 1.0

    cb = ContinuousBatcher(n_slots=2, step_time_fn=step_time)
    cb.submit(Request(0, arrival_s=5.0, max_new=3))
    done = cb.run()
    assert done[0].done_s == pytest.approx(8.0)
    assert calls == [1, 1, 1], f"idle gap billed as decode steps: {calls}"


def test_continuous_batcher_idle_jump_with_fractional_steps():
    """With sub-second decode steps the old code admitted late (clock creeps
    past the arrival in step_time increments); the jump admits on time."""
    cb = ContinuousBatcher(n_slots=1, step_time_fn=lambda n: 0.3)
    cb.submit(Request(0, arrival_s=1.0, max_new=2))
    done = cb.run()
    assert done[0].done_s == pytest.approx(1.6)


def test_continuous_batcher_mid_flight_join_unchanged():
    """The idle-jump fix must not change behavior while slots are active."""
    cb = ContinuousBatcher(n_slots=2, step_time_fn=lambda n: 1.0)
    cb.submit(Request(0, arrival_s=0.0, max_new=4))
    cb.submit(Request(1, arrival_s=1.5, max_new=2))
    done = {r.rid: r for r in cb.run()}
    assert done[0].done_s == 4.0 and done[1].done_s == 4.0


def test_continuous_batcher_idle_gap_between_bursts():
    """Second burst long after the first: both complete, no wasted steps."""
    steps = []
    cb = ContinuousBatcher(n_slots=1, step_time_fn=lambda n: (steps.append(n), 1.0)[1])
    cb.submit(Request(0, arrival_s=0.0, max_new=2))
    cb.submit(Request(1, arrival_s=100.0, max_new=2))
    done = {r.rid: r for r in cb.run(max_steps=10)}
    assert done[0].done_s == 2.0
    assert done[1].done_s == pytest.approx(102.0)
    assert len(steps) == 4


# ------------------------------------------------------------- fleet runtime

def _cfg(sla_s=0.3):
    # deterministic: wall-clock scheduler overhead would make the fleet-vs-
    # engine comparison nondeterministic
    return engine.EngineConfig(sla_s=sla_s, include_scheduler_overhead=False)


def test_fleet_n1_reproduces_single_stream_engine():
    """With one stream and a transparent batcher (max_batch=1, free capacity)
    the fleet path is the single-stream engine, frame for frame."""
    prof, cfg = _profile(), _cfg()
    trace = bandwidth.synthetic_trace("4g", "driving", steps=40, seed=3)
    st_engine = engine.JanusEngine(prof, cfg).run_trace(trace, 40, "janus")
    fs = fleet.FleetRuntime(prof, cfg, [fleet.StreamSpec(trace, 40)],
                            cloud=fleet.CloudTierConfig(max_batch=1)).run()
    st_fleet = fs.per_stream[0]
    assert len(st_fleet.frames) == 40
    np.testing.assert_allclose([f.latency_s for f in st_fleet.frames],
                               [f.latency_s for f in st_engine.frames])
    assert [f.split for f in st_fleet.frames] == [f.split for f in st_engine.frames]
    assert [f.alpha for f in st_fleet.frames] == [f.alpha for f in st_engine.frames]
    assert st_fleet.violation_ratio == st_engine.violation_ratio
    assert fs.avg_queue_s == 0.0


def test_fleet_default_cloud_config_transparent_for_one_stream():
    assert fleet.default_cloud_config(1).max_batch == 1
    assert fleet.default_cloud_config(64).max_batch == 8


def test_fleet_stream_isolation_of_estimator_state():
    """A blocked stream must not poison a fast stream's bandwidth belief:
    the fast stream keeps offloading (split 0) while the blocked one fails
    over to device-only (split N+1)."""
    prof, cfg = _profile(), _cfg(sla_s=1.0)
    n = prof.n_layers
    blocked = bandwidth.NetworkTrace(np.full(12, 1e3), 0.042, "blocked")
    fast = bandwidth.NetworkTrace(np.full(12, 80e6), 0.002, "fast")
    fs = fleet.FleetRuntime(prof, cfg, [fleet.StreamSpec(blocked, 12),
                                        fleet.StreamSpec(fast, 12)]).run()
    splits_blocked = [f.split for f in fs.per_stream[0].frames[1:]]
    splits_fast = [f.split for f in fs.per_stream[1].frames[1:]]
    assert all(s == n + 1 for s in splits_blocked), splits_blocked
    assert all(s == 0 for s in splits_fast), splits_fast


def test_fleet_per_stream_sla_overrides():
    """Per-stream SLA drives per-stream decisions: a stream with an
    impossible SLA reports violations while a lax one does not."""
    prof, cfg = _profile(), _cfg(sla_s=10.0)
    trace = bandwidth.NetworkTrace(np.full(8, 20e6), 0.01, "steady")
    rt = fleet.FleetRuntime(prof, cfg, [
        fleet.StreamSpec(trace, 8, sla_s=1e-6),
        fleet.StreamSpec(trace, 8),
        fleet.StreamSpec(trace, 8, sla_s=0.0),
    ])
    # a falsy-but-set override (0.0) must not fall back to the fleet default
    assert [e.cfg.sla_s for e in rt.engines] == [1e-6, 10.0, 0.0]
    fs = fleet.FleetRuntime(prof, cfg, [fleet.StreamSpec(trace, 8, sla_s=1e-6),
                                        fleet.StreamSpec(trace, 8)]).run()
    assert fs.per_stream[0].violation_ratio == 1.0
    assert fs.per_stream[1].violation_ratio == 0.0


def test_fleet_cloud_saturation_causes_queueing_delay():
    """Many cloud-offloading streams on one executor queue up; ample capacity
    makes the queueing (mostly) vanish. Total work is identical."""
    prof = _profile()
    cfg = _cfg(sla_s=0.5)
    n_streams, frames = 8, 12
    fast = [bandwidth.NetworkTrace(np.full(frames, 80e6), 0.002, f"fast{i}")
            for i in range(n_streams)]

    def run(capacity):
        streams = [fleet.StreamSpec(t, frames) for t in fast]
        return fleet.FleetRuntime(
            prof, cfg, streams,
            cloud=fleet.CloudTierConfig(capacity=capacity, max_batch=1)).run()

    tight = run(1)
    ample = run(n_streams)
    assert len(tight.all_frames) == n_streams * frames
    assert tight.avg_queue_s > ample.avg_queue_s
    assert tight.avg_queue_s > 0.0
    assert tight.p99_latency_s > ample.p99_latency_s
    assert tight.cloud_utilization > ample.cloud_utilization
    # queueing delay is extra latency, never a discount
    for st_t, st_a in zip(tight.per_stream, ample.per_stream):
        for ft, fa in zip(st_t.frames, st_a.frames):
            assert ft.latency_s >= fa.latency_s - 1e-12


def test_fleet_microbatching_amortizes_cloud_work():
    """With batching enabled, concurrent frames share executors: mean batch
    size exceeds 1 and total cloud busy time shrinks vs unbatched."""
    prof, cfg = _profile(), _cfg(sla_s=0.5)
    frames, n_streams = 10, 8
    traces = [bandwidth.NetworkTrace(np.full(frames, 80e6), 0.002, f"s{i}")
              for i in range(n_streams)]

    def run(max_batch):
        streams = [fleet.StreamSpec(t, frames) for t in traces]
        return fleet.FleetRuntime(
            prof, cfg, streams,
            cloud=fleet.CloudTierConfig(capacity=2, max_batch=max_batch,
                                        max_wait_s=0.02)).run()

    batched, unbatched = run(8), run(1)
    assert batched.avg_batch_size > 1.0
    assert batched.cloud_busy_s < unbatched.cloud_busy_s


# ------------------------------------------- fleet real-math (execute) path

def _exec_setup():
    cfg = vit_lib.ViTConfig(img_res=32, patch=8, n_layers=4, d_model=32,
                            n_heads=2, d_ff=64, n_classes=8)
    params = param_lib.init_params(vit_lib.specs(cfg), jax.random.key(0))
    images = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    eng_cfg = engine.EngineConfig(sla_s=0.5, execute=True,
                                  include_scheduler_overhead=False)
    return cfg, params, images, eng_cfg


def _exec_fleet(n_streams, frames, max_batch, capacity=4):
    cfg, params, images, eng_cfg = _exec_setup()
    prof = _profile()
    traces = [bandwidth.NetworkTrace(np.full(frames, 80e6), 0.002, f"s{i}")
              for i in range(n_streams)]
    rt = fleet.FleetRuntime(
        prof, eng_cfg, [fleet.StreamSpec(t, frames) for t in traces],
        cloud=fleet.CloudTierConfig(capacity=capacity, max_batch=max_batch,
                                    max_wait_s=0.02),
        model_cfg=cfg, params=params)
    return rt, rt.run(images=images), images


def test_fleet_batched_execute_logits_equal_per_item():
    """Micro-batched cloud partitions (one stacked forward per geometry
    group) produce the same logits as per-item execution (max_batch=1) and
    as the reference split_inference round trip."""
    n_streams, frames = 4, 3
    rt_b, fs_batched, images = _exec_fleet(n_streams, frames, max_batch=n_streams)
    rt_u, fs_unbatched, _ = _exec_fleet(n_streams, frames, max_batch=1)
    assert fs_batched.avg_batch_size > 1.0, "steady streams must co-batch"

    cfg, prof = rt_b.model_cfg, rt_b.engines[0].profile
    n_exec, n_prof = cfg.n_layers, prof.n_layers
    for st_b, st_u in zip(fs_batched.per_stream, fs_unbatched.per_stream):
        for fb, fu in zip(st_b.frames, st_u.frames):
            assert fb.logits is not None and fu.logits is not None
            assert (fb.alpha, fb.split) == (fu.alpha, fu.split)
            np.testing.assert_allclose(np.asarray(fb.logits),
                                       np.asarray(fu.logits),
                                       atol=1e-5, rtol=1e-5)
            sched = tuple(pruning.make_schedule(prof.schedule_kind, fb.alpha,
                                                n_exec, cfg.num_tokens))
            split_exec = n_exec + 1 if fb.split >= n_prof + 1 else \
                min(round(fb.split * n_exec / n_prof), n_exec)
            ref, _ = engine.split_inference(rt_b.params, cfg, images, sched,
                                            split_exec, quantize=True)
            np.testing.assert_allclose(np.asarray(fb.logits), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)


def test_execute_fleet_without_images_raises():
    """An execute runtime given no images refuses to run rather than quietly
    simulating without the model."""
    cfg, params, _, eng_cfg = _exec_setup()
    trace = bandwidth.NetworkTrace(np.full(2, 80e6), 0.002, "s0")
    rt = fleet.FleetRuntime(_profile(), eng_cfg, [fleet.StreamSpec(trace, 2)],
                            model_cfg=cfg, params=params)
    with pytest.raises(ValueError, match="needs images"):
        rt.run()


def test_execute_at_profiled_geometry_runs_the_decision():
    """When the executed model has the profiled geometry (as the served
    ViT-L@384 does), the executed plan is the decision itself — baseline
    policies included, whose fixed ToMe schedule their alpha=0 does not
    encode."""
    prof = _profile()
    cfg = vit_lib.ViTConfig(img_res=48, patch=4, n_layers=prof.n_layers,
                            d_model=32, n_heads=2, d_ff=64, n_classes=8)
    assert cfg.num_tokens == prof.x0
    params = param_lib.init_params(vit_lib.specs(cfg), jax.random.key(0))
    images = jax.random.normal(jax.random.key(1), (1, 48, 48, 3))
    eng = engine.JanusEngine(prof, engine.EngineConfig(sla_s=0.5, execute=True),
                             model_cfg=cfg, params=params)
    trace = bandwidth.NetworkTrace(np.full(2, 80e6), 0.002, "s0")
    for policy in ("janus", "cloud"):
        step = eng.plan_frame(0, trace, policy,
                              bandwidth.HarmonicMeanEstimator(cold_start_bps=80e6),
                              images=images)
        assert step.exec_plan.schedule == tuple(step.decision.schedule)
        assert step.exec_plan.split == step.decision.split
        assert step.exec_plan.logits.shape == (1, cfg.n_classes)


def test_fleet_shares_one_plan_cache_across_streams():
    """Same-geometry streams compile each partition program once fleet-wide:
    the shared cache traces exactly (device + cloud) once."""
    rt, fs, _ = _exec_fleet(4, 2, max_batch=4)
    assert all(e.plan_cache is rt.plan_cache for e in rt.engines)
    assert rt.plan_cache.traces == 2, \
        f"expected 1 device + 1 cloud trace, got {rt.plan_cache.traces}"
    assert rt.plan_cache.hits > 0


def test_fleet_n1_execute_matches_run_trace():
    """--streams 1 --execute reproduces the single-stream engine: same
    latencies, payloads, and logits."""
    cfg, params, images, eng_cfg = _exec_setup()
    prof = _profile()
    trace = bandwidth.synthetic_trace("wifi", "walking", steps=8, seed=5)
    st_engine = engine.JanusEngine(prof, eng_cfg, model_cfg=cfg, params=params) \
        .run_trace(trace, 8, "janus", images=images)
    fs = fleet.FleetRuntime(prof, eng_cfg, [fleet.StreamSpec(trace, 8)],
                            cloud=fleet.CloudTierConfig(max_batch=1),
                            model_cfg=cfg, params=params).run(images=images)
    st_fleet = fs.per_stream[0]
    np.testing.assert_allclose([f.latency_s for f in st_fleet.frames],
                               [f.latency_s for f in st_engine.frames])
    assert [f.payload_bytes for f in st_fleet.frames] == \
        [f.payload_bytes for f in st_engine.frames]
    for ff, fe in zip(st_fleet.frames, st_engine.frames):
        np.testing.assert_allclose(np.asarray(ff.logits), np.asarray(fe.logits),
                                   atol=1e-5, rtol=1e-5)


def test_fleet_frames_complete_and_stats_sane():
    prof, cfg = _profile(), _cfg()
    streams = [
        fleet.StreamSpec(bandwidth.synthetic_trace("5g", "walking", steps=10,
                                                   seed=s), 10)
        for s in range(6)
    ]
    fs = fleet.FleetRuntime(prof, cfg, streams).run()
    assert len(fs.all_frames) == 60
    assert 0.0 <= fs.violation_ratio <= 1.0
    assert 0.0 <= fs.cloud_utilization <= 1.0
    assert fs.horizon_s > 0
    assert fs.p99_latency_s >= fs.p50_latency_s > 0


def test_fleet_closed_loop_never_drops_and_capacity_stays_static():
    """The workload hooks must be no-ops for classic closed-loop fleets:
    zero drops, a single-entry capacity timeline, and the pre-autoscale
    utilization denominator (capacity * horizon)."""
    prof, cfg = _profile(), _cfg()
    streams = [
        fleet.StreamSpec(bandwidth.synthetic_trace("4g", "walking", steps=8,
                                                   seed=s), 8)
        for s in range(4)
    ]
    fs = fleet.FleetRuntime(prof, cfg, streams).run()
    assert fs.dropped_per_stream == [0, 0, 0, 0]
    assert fs.drop_ratio == 0.0
    assert fs.capacity_timeline == [(0.0, fs.capacity)]
    assert fs.capacity_seconds == pytest.approx(fs.capacity * fs.horizon_s)
