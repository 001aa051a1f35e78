"""Bucketed, sharded real execution (docs/execution.md).

Covers the continuous-batching fast path end to end:

  * bucket-edge policy properties + JSON round trip (``core.bucketing``)
  * pad-aware ToMe merge == unpadded merge on the real tokens
  * padded cloud forward == unpadded forward (exact masking: logits are
    bit-independent of pad *values*; vs the unpadded program they match to
    float-reassociation tolerance — XLA picks different reduction strategies
    at different extents, worst observed ~5e-7 f32)
  * ``run_cloud_batch`` join-vs-stack parity under mixed α at a shared split,
    with the retrace count bounded by the bucket-edge table
  * fleet integration: bucketing changes neither the simulated timing plane
    nor the logits, and cuts compiled cloud geometries
  * mesh-sharded execution (1-device dp mesh) reproduces the unsharded path
  * at split 0 and split n + 1 the partition with no transformer block is
    folded into its neighbour's program, rounding as the two programs did,
    and interior splits dispatch what they always did (a fleet's folded
    frames: ``tests/test_spans.py``)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import small_model_profile as _profile

from repro.core import bandwidth, engine, pruning, tome
from repro.core.bucketing import BucketingConfig, BucketTable, bucket_edges
from repro.core.scheduler import Decision
from repro.models import param as param_lib
from repro.models import vit as vit_lib
from repro.serving import fleet

# every alpha below shares the cloud schedule suffix (1, 1) at SPLIT while
# entering the cloud with a different token count (45, 44, 40, 37, 32, 27,
# 17, 7) — the saturating exponential schedule is what makes mixed-alpha
# continuous batching possible at all (see docs/execution.md)
ALPHAS = (0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)
SPLIT = 4

# f32 tolerance for padded-vs-unpadded logits: masking is mathematically
# exact (pad contributions are exactly zero) but XLA reassociates reductions
# differently at different extents; worst observed diff is ~5e-7
PAD_ATOL = 2e-6


def _cfg50():
    # num_tokens = (56/8)^2 + 1 = 50
    return vit_lib.ViTConfig(img_res=56, patch=8, n_layers=6, d_model=32,
                             n_heads=2, d_ff=64, n_classes=8)


def _params(cfg):
    return param_lib.init_params(vit_lib.specs(cfg), jax.random.key(0))


def _plan_for(cfg, params, alpha, split, seed=0):
    img = jax.random.normal(jax.random.key(100 + seed),
                            (1, cfg.img_res, cfg.img_res, 3))
    sched = tuple(pruning.make_schedule("exponential", alpha, cfg.n_layers,
                                        cfg.num_tokens))
    x, sizes = engine.device_forward(params, cfg, img, sched, split)
    return engine.ExecPlan(sched, split, x=x, sizes=sizes)


# ------------------------------------------------------------- bucket policy

def test_bucket_edges_few_counts_identity():
    assert bucket_edges([7, 17, 27], 4) == (7, 17, 27)
    assert bucket_edges([], 4) == ()
    assert bucket_edges([5, 5, 5], 1) == (5,)


def test_bucket_edges_subsets_and_covers():
    counts = [7, 17, 27, 32, 37, 40, 44, 45]
    for n in (1, 2, 3, 4):
        edges = bucket_edges(counts, n)
        assert len(edges) <= n
        assert edges[-1] == max(counts), "max must always be an edge"
        assert set(edges) <= set(counts)
        for c in counts:  # every count rounds up to some edge
            assert any(e >= c for e in edges)


def test_bucket_table_edge_for_rounds_up():
    table = BucketTable({4: (7, 45)})
    assert table.edge_for(4, 7) == 7
    assert table.edge_for(4, 8) == 45
    assert table.edge_for(4, 45) == 45
    # off-table counts and splits fall back to the exact geometry
    assert table.edge_for(4, 46) == 46
    assert table.edge_for(5, 12) == 12


def test_bucket_table_build_covers_alpha_grid():
    cfg = _cfg50()
    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=3))
    for a in ALPHAS:
        sched = pruning.make_schedule("exponential", a, cfg.n_layers,
                                      cfg.num_tokens)
        counts = pruning.token_counts(cfg.num_tokens, sched)
        for s in range(cfg.n_layers + 1):
            assert table.edge_for(s, counts[s]) >= counts[s]
            assert table.edge_for(s, counts[s]) in table.edges_by_split[s]
    assert table.n_cells == sum(len(e) for e in table.edges_by_split.values())


def test_bucket_table_json_roundtrip():
    cfg = _cfg50()
    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=2))
    back = BucketTable.from_json(table.as_json())
    assert back.edges_by_split == table.edges_by_split
    assert back.config.n_edges == table.config.n_edges


def test_bucketing_config_validates():
    with pytest.raises(ValueError):
        BucketingConfig(n_edges=0)


# --------------------------------------------------------- pad-aware merging

def test_tome_merge_padded_matches_unpadded_on_real_tokens():
    key = jax.random.key(3)
    b, t, d, r = 2, 21, 16, 5
    x = jax.random.normal(jax.random.fold_in(key, 0), (b, t, d))
    metric = jax.random.normal(jax.random.fold_in(key, 1), (b, t, d))
    sizes = 1.0 + jax.random.uniform(jax.random.fold_in(key, 2), (b, t))
    ref_x, ref_sizes = tome.tome_merge(x, metric, sizes, r)
    for pad in (1, 4, 9):
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        mp = jnp.pad(metric, ((0, 0), (0, pad), (0, 0)))
        sp = jnp.pad(sizes, ((0, 0), (0, pad)))
        out_x, out_sizes = tome.tome_merge_padded(xp, mp, sp, r)
        nr = t - r
        np.testing.assert_allclose(np.asarray(out_x[:, :nr]),
                                   np.asarray(ref_x), atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out_sizes[:, :nr]),
                                   np.asarray(ref_sizes), atol=1e-6)
        assert bool(jnp.all(out_sizes[:, nr:] == 0.0)), "pads stay at the tail"


def test_tome_merge_padded_validates_r():
    x = jnp.zeros((1, 8, 4))
    s = jnp.ones((1, 8))
    with pytest.raises(ValueError):
        tome.tome_merge_padded(x, x, s, 4)  # r must be < ceil(n/2)


# ------------------------------------------------------ padded cloud forward

def test_padded_cloud_forward_matches_unpadded():
    cfg, params = _cfg50(), None
    params = _params(cfg)
    cache = engine.CompiledPlanCache()
    for alpha in (0.3, 0.6, 0.9):
        plan = _plan_for(cfg, params, alpha, SPLIT)
        ref = engine.cloud_forward(params, cfg, plan.x, plan.sizes,
                                   plan.schedule, SPLIT)
        t = plan.x.shape[1]
        for pad in (0, 3, 8):
            xp = jnp.pad(plan.x, ((0, 0), (0, pad), (0, 0)))
            sp = jnp.pad(plan.sizes, ((0, 0), (0, pad)))
            fn = cache.cloud_padded_fn(cfg, plan.schedule[SPLIT:], SPLIT, xp)
            out = fn(params, xp, sp)
            assert not bool(jnp.any(jnp.isnan(out)))
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=PAD_ATOL, rtol=PAD_ATOL,
                                       err_msg=f"alpha={alpha} T={t} pad={pad}")


def test_padded_logits_bit_independent_of_pad_values():
    """The exactness claim: pads are *masked*, not merely attenuated, so the
    logits are bit-identical whatever garbage the pad slots hold."""
    cfg = _cfg50()
    params = _params(cfg)
    plan = _plan_for(cfg, params, 0.5, SPLIT)
    pad = 6
    cache = engine.CompiledPlanCache()
    sp = jnp.pad(plan.sizes, ((0, 0), (0, pad)))
    xp_zeros = jnp.pad(plan.x, ((0, 0), (0, pad), (0, 0)))
    garbage = 1e3 * jax.random.normal(jax.random.key(9),
                                      (plan.x.shape[0], pad, plan.x.shape[2]))
    xp_garbage = jnp.concatenate([plan.x, garbage], axis=1)
    fn = cache.cloud_padded_fn(cfg, plan.schedule[SPLIT:], SPLIT, xp_zeros)
    out0 = fn(params, xp_zeros, sp)
    out1 = fn(params, xp_garbage, sp)
    assert np.array_equal(np.asarray(out0), np.asarray(out1))


# ----------------------------------------------- run_cloud_batch (join/stack)

def test_run_cloud_batch_bucketed_parity_mixed_alpha():
    """Mixed α at a shared split: all eight plans share the schedule suffix,
    differ in token count, and must produce the per-plan slow-path logits
    after bucketed stacking."""
    cfg = _cfg50()
    params = _params(cfg)
    plans, refs = [], []
    for i, a in enumerate(ALPHAS):
        plan = _plan_for(cfg, params, a, SPLIT, seed=i)
        refs.append(engine.cloud_forward(params, cfg, plan.x, plan.sizes,
                                         plan.schedule, SPLIT))
        plans.append(plan)
    suffixes = {p.schedule[SPLIT:] for p in plans}
    assert suffixes == {(1, 1)}, "geometry precondition drifted"
    counts = {p.x.shape[1] for p in plans}
    assert len(counts) == len(ALPHAS), "geometry precondition drifted"

    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=2))
    cache = engine.CompiledPlanCache()
    engine.run_cloud_batch(cache, cfg, params, plans, buckets=table)
    for plan, ref in zip(plans, refs):
        np.testing.assert_allclose(np.asarray(plan.logits), np.asarray(ref),
                                   atol=PAD_ATOL, rtol=PAD_ATOL)
    # retraces bounded by the split's edge count, beating one-per-count
    n_padded = cache.traces_by_kind.get("cloud_padded", 0)
    assert n_padded <= len(table.edges_by_split[SPLIT])
    assert n_padded < len(counts)

    # exact-geometry path needs one compiled program per distinct count
    plans2 = [_plan_for(cfg, params, a, SPLIT, seed=i)
              for i, a in enumerate(ALPHAS)]
    cache2 = engine.CompiledPlanCache()
    engine.run_cloud_batch(cache2, cfg, params, plans2)
    assert cache2.traces_by_kind.get("cloud", 0) == len(counts)
    for plan, ref in zip(plans2, refs):
        np.testing.assert_allclose(np.asarray(plan.logits), np.asarray(ref),
                                   atol=PAD_ATOL, rtol=PAD_ATOL)


# ------------------------------------------------------------------ fleet

def _bucketed_fleet(bucketing):
    cfg = _cfg50()
    params = _params(cfg)
    images = jax.random.normal(jax.random.key(1),
                               (1, cfg.img_res, cfg.img_res, 3))
    eng_cfg = engine.EngineConfig(sla_s=0.5, execute=True,
                                  include_scheduler_overhead=False)
    prof = _profile()
    frames = 3
    streams = [fleet.StreamSpec(
        bandwidth.synthetic_trace("4g", "driving", steps=frames, seed=s),
        frames) for s in range(6)]
    rt = fleet.FleetRuntime(prof, eng_cfg, streams,
                            cloud=fleet.CloudTierConfig(capacity=2,
                                                        max_batch=6,
                                                        max_wait_s=0.02),
                            model_cfg=cfg, params=params, bucketing=bucketing)
    return rt, rt.run(images=images)


def test_fleet_bucketing_keeps_timing_and_logits():
    """Bucketing changes which compiled geometry fills the logits — never the
    simulated timing plane (accounting is table-driven) and never the values
    beyond float reassociation."""
    rt0, fs0 = _bucketed_fleet(None)
    rt1, fs1 = _bucketed_fleet(BucketingConfig(n_edges=2))
    assert rt1.buckets is not None and rt1.buckets.n_cells > 0
    for st0, st1 in zip(fs0.per_stream, fs1.per_stream):
        for f0, f1 in zip(st0.frames, st1.frames):
            assert (f0.alpha, f0.split) == (f1.alpha, f1.split)
            assert f0.latency_s == f1.latency_s
            assert f0.payload_bytes == f1.payload_bytes
            assert f0.logits is not None and f1.logits is not None
            np.testing.assert_allclose(np.asarray(f0.logits),
                                       np.asarray(f1.logits),
                                       atol=1e-5, rtol=1e-5)
    cloud0 = rt0.plan_cache.traces_by_kind.get("cloud", 0)
    padded1 = rt1.plan_cache.traces_by_kind.get("cloud_padded", 0)
    assert padded1 <= max(cloud0, 1), \
        f"bucketing must not inflate cloud geometries ({padded1} > {cloud0})"


def test_fleet_accepts_prebuilt_bucket_table():
    cfg = _cfg50()
    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=2))
    rt, fs = None, None
    rt, fs = _bucketed_fleet(table)
    assert rt.buckets is table
    assert all(f.logits is not None for f in fs.all_frames)


# ------------------------------------------------------------- mesh sharding

def test_sharded_cache_matches_unsharded_on_one_device_mesh():
    """With the (1, 1) host mesh the dp rules lower to no-op shardings, so
    the sharded cache must reproduce the unsharded logits bit for bit."""
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import make_rules

    cfg = _cfg50()
    params = _params(cfg)
    rules = make_rules("dp", make_host_mesh())
    placed = engine.shard_params(params, cfg, rules)
    plans = [_plan_for(cfg, params, a, SPLIT, seed=i)
             for i, a in enumerate((0.3, 0.7))]
    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=2))
    sharded = engine.CompiledPlanCache(rules=rules)
    engine.run_cloud_batch(sharded, cfg, placed, plans, buckets=table)
    plain_plans = [_plan_for(cfg, params, a, SPLIT, seed=i)
                   for i, a in enumerate((0.3, 0.7))]
    plain = engine.CompiledPlanCache()
    engine.run_cloud_batch(plain, cfg, params, plain_plans, buckets=table)
    for p_sharded, p_plain in zip(plans, plain_plans):
        assert np.array_equal(np.asarray(p_sharded.logits),
                              np.asarray(p_plain.logits))


def test_fleet_mesh_rules_single_device_parity():
    cfg = _cfg50()
    params = _params(cfg)
    images = jax.random.normal(jax.random.key(1),
                               (1, cfg.img_res, cfg.img_res, 3))
    eng_cfg = engine.EngineConfig(sla_s=0.5, execute=True,
                                  include_scheduler_overhead=False)
    prof = _profile()
    trace = bandwidth.NetworkTrace(np.full(3, 80e6), 0.002, "s0")

    from repro.launch.mesh import make_host_mesh
    from repro.sharding.rules import make_rules

    def run(mesh_rules):
        rt = fleet.FleetRuntime(prof, eng_cfg,
                                [fleet.StreamSpec(trace, 3)],
                                cloud=fleet.CloudTierConfig(max_batch=1),
                                model_cfg=cfg, params=params,
                                mesh_rules=mesh_rules)
        return rt.run(images=images)

    fs_plain = run(None)
    fs_mesh = run(make_rules("dp", make_host_mesh()))
    for f0, f1 in zip(fs_plain.all_frames, fs_mesh.all_frames):
        assert f0.latency_s == f1.latency_s
        np.testing.assert_allclose(np.asarray(f0.logits),
                                   np.asarray(f1.logits),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------- partitions with no transformer block

def test_interior_split_dispatches_what_it_did():
    """At an interior split the device partition, the codec and the padded
    cloud program run as before, and nothing is folded."""
    cfg = _cfg50()
    params = _params(cfg)
    img = jax.random.normal(jax.random.key(100), (1, cfg.img_res, cfg.img_res, 3))
    prof = _profile()
    eng = engine.JanusEngine(prof, engine.EngineConfig(sla_s=0.5, execute=True),
                             model_cfg=cfg, params=params)
    # the profile's split that the engine maps onto SPLIT of the stand-in
    split = SPLIT * prof.n_layers // cfg.n_layers
    plan, payload = eng.execute_device(Decision(0.5, split, 0.0, True, ()), img)
    assert plan.split == SPLIT
    assert plan.x is not None and plan.images is None and payload > 0
    assert plan.logits is None
    table = BucketTable.build(cfg, ALPHAS, config=BucketingConfig(n_edges=2))
    engine.run_cloud_batch(eng.plan_cache, cfg, params, [plan], buckets=table)
    pc = eng.plan_cache
    assert pc.traces_by_kind == {"device": 1, "cloud_padded": 1}
    assert pc.hits + pc.misses == 2
    assert pc.folded == {"embed": 0, "head": 0}
    ref, _ = engine.split_inference(params, cfg, img, plan.schedule, SPLIT,
                                    quantize=True)
    np.testing.assert_allclose(np.asarray(plan.logits), np.asarray(ref),
                               atol=PAD_ATOL, rtol=PAD_ATOL)


@pytest.mark.parametrize("split", ["cloud_only", "device_only"])
def test_folded_program_rounds_as_the_two_programs(split):
    """In bf16 a folded program equals the two programs it replaces bit for
    bit: the value at the seam is stored in bf16 as a program boundary
    stores it, so no near-tied merge can flip."""
    cfg = vit_lib.ViTConfig(img_res=56, patch=8, n_layers=6, d_model=32,
                            n_heads=2, d_ff=64, n_classes=8,
                            dtype=jnp.bfloat16)
    params = param_lib.init_params(vit_lib.specs(cfg), jax.random.key(0),
                                   dtype=cfg.dtype)
    imgs = jax.random.normal(jax.random.key(5), (3, cfg.img_res, cfg.img_res, 3))
    sched = tuple(pruning.make_schedule("exponential", 0.7, cfg.n_layers,
                                        cfg.num_tokens))
    cache = engine.CompiledPlanCache()
    s = 0 if split == "cloud_only" else cfg.n_layers + 1
    if s == 0:
        got = cache.cloud_embed_fn(cfg, sched, imgs)(params, imgs)
    else:
        got = cache.device_fn(cfg, sched, s, imgs)(params, imgs)
    ref, _ = engine.split_inference(params, cfg, imgs, sched, s)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32))
