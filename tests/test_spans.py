"""The ``janus/`` spans and program names of the served path
(``repro.runtime.spans``, ``engine.CompiledPlanCache``, ``core.tome``).

A fleet of the ``--smoke-model`` stand-in runs under ``--execute`` once
with ``jax.profiler.trace`` around it (cold: every program is built then)
and once without. The trace's host plane must hold one span per frame and
per micro-batch with the args the docs promise, the programs must carry
stable ``jit_janus_*`` names and the ToMe merge its ``tome_merge`` scope,
and the profiler must change nothing that the run returns. The same fleet
shows the folded partitions: at split 0 and split n + 1 the partition
with no transformer block runs inside its neighbour's program.
"""
import dataclasses
import pathlib
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, pruning
from repro.core.scheduler import Decision
from repro.launch import serve
from repro.runtime import spans

# 4G/static on the paper's timing profile: most frames offload at alpha
# near 0.27 (ToMe merges in padded cloud micro-batches that embed the raw
# frames), some run device-only (their device program ends with the head)
ARGV = ["--streams", "4", "--frames", "3", "--network", "4g",
        "--mobility", "static", "--sla-ms", "150", "--seed", "0",
        "--execute", "--smoke-model", "--bucket-edges", "2",
        "--telemetry-sample", "0"]


def host_spans(trace_dir) -> list[tuple[str, dict]]:
    """(name, args) of every ``janus/`` span on the trace's host plane."""
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in spans.NAMES:
                    out.append((e.name, {k: str(v) for k, v in e.stats}))
    return out


def _build():
    args = serve.build_parser().parse_args(ARGV)
    profile, eng_cfg, cfg, params, images = serve.setup(args)
    # measured planner wall time billed into the clock would make the two
    # runs' FleetStats differ for a reason of its own
    eng_cfg = dataclasses.replace(eng_cfg, include_scheduler_overhead=False)
    rt = serve.build_fleet(args, serve.spec_from_args(args), profile,
                           eng_cfg, cfg, params)
    return rt, cfg, params, images


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    rt, cfg, params, images = _build()
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        fs_on = rt.run(images=images)
        jax.block_until_ready([f.logits for f in fs_on.all_frames])
    folded_on = dict(rt.plan_cache.folded)
    fs_off = rt.run(images=images)
    return dict(rt=rt, cfg=cfg, params=params, images=images, fs_on=fs_on,
                fs_off=fs_off, folded_on=folded_on, spans=host_spans(d))


def _frame_ids(fs) -> list[str]:
    return [f"{si}:{fi}" for si, st in enumerate(fs.per_stream)
            for fi in range(len(st.frames))]


def _block_frames(fs):
    """Frames whose device partition runs transformer blocks: a cloud-only
    frame (split 0) dispatches no device program."""
    return [f for f in fs.all_frames if f.split != 0]


@pytest.mark.parametrize("name", [spans.PLAN_FRAME, spans.DECIDE,
                                  spans.DEVICE_PARTITION])
def test_one_span_per_frame(served, name):
    fs = served["fs_on"]
    frames = fs.all_frames
    if name == spans.DEVICE_PARTITION:
        frames = _block_frames(fs)
        assert len(frames) < len(fs.all_frames), \
            "the scenario should hold cloud-only frames"
    n = sum(1 for nm, _ in served["spans"] if nm == name)
    assert n == len(frames)


def test_plan_frame_args_name_every_frame(served):
    got = sorted(f"{a['stream']}:{a['frame']}" for nm, a in served["spans"]
                 if nm == spans.PLAN_FRAME)
    assert got == sorted(_frame_ids(served["fs_on"]))


def test_device_partition_carries_the_split(served):
    got = Counter(int(a["split"]) for nm, a in served["spans"]
                  if nm == spans.DEVICE_PARTITION)
    assert got == Counter(f.split for f in _block_frames(served["fs_on"]))
    assert 0 not in got


def test_one_cloud_batch_span_per_micro_batch(served):
    """Every micro-batch is one ``janus/cloud_batch`` whose ``ids`` name its
    members; a device-only frame's head runs in its device program, so it
    has no cloud batch of its own."""
    fs, n = served["fs_on"], served["rt"].engines[0].profile.n_layers
    batches = [a for nm, a in served["spans"] if nm == spans.CLOUD_BATCH]
    device_only = {f"{si}:{fi}" for si, st in enumerate(fs.per_stream)
                   for fi, f in enumerate(st.frames) if f.split == n + 1}
    assert device_only, "the scenario should hold device-only frames"
    assert sorted(int(a["frames"]) for a in batches) == sorted(fs.batch_sizes)
    ids = sorted(i for a in batches for i in a["ids"].split(","))
    assert ids == sorted(set(_frame_ids(fs)) - device_only)


def test_program_build_once_per_miss(served):
    builds = [a["kind"] for nm, a in served["spans"]
              if nm == spans.PROGRAM_BUILD]
    pc = served["rt"].plan_cache
    assert len(builds) == pc.misses
    assert Counter(builds) == Counter(pc.traces_by_kind)
    n_call = sum(1 for nm, _ in served["spans"] if nm == spans.CLOUD_CALL)
    assert n_call == sum(1 for nm, _ in served["spans"]
                         if nm == spans.CLOUD_BATCH)


def test_profiler_changes_nothing_returned(served):
    """``FleetStats`` and every frame's logits are bit-identical with the
    profiler on (cold) and off (warm)."""
    on, off = served["fs_on"], served["fs_off"]

    def bare(fs):
        return dataclasses.replace(fs, per_stream=[
            dataclasses.replace(st, frames=[
                dataclasses.replace(f, logits=None) for f in st.frames])
            for st in fs.per_stream])

    assert bare(on) == bare(off)
    for f_on, f_off in zip(on.all_frames, off.all_frames, strict=True):
        np.testing.assert_array_equal(np.asarray(f_on.logits),
                                      np.asarray(f_off.logits))


# ------------------------------------- partitions with no transformer block

def _by_split(served) -> dict[int, list]:
    fs, n = served["fs_on"], served["rt"].engines[0].profile.n_layers
    by_split = {s: [f for f in fs.all_frames if f.split == s]
                for s in (0, n + 1)}
    assert by_split[0] and by_split[n + 1], "geometry precondition drifted"
    assert len(by_split[0]) + len(by_split[n + 1]) == len(fs.all_frames)
    return by_split


def _exec_geometry(rt, frame):
    dec = Decision(frame.alpha, frame.split, 0.0, True, ())
    return rt.engines[0]._exec_geometry(dec)


def test_folded_partitions_match_split_inference(served):
    """Cloud-only and device-only frames of a fleet tick give the logits of
    the unbatched two-program round trip."""
    rt, cfg = served["rt"], served["cfg"]
    for f in served["fs_on"].all_frames:
        sched, split = _exec_geometry(rt, f)
        ref, _ = engine.split_inference(served["params"], cfg,
                                        served["images"], sched, split)
        np.testing.assert_allclose(np.asarray(f.logits), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6,
                                   err_msg=f"alpha={f.alpha} split={f.split}")


def test_folded_partitions_build_no_program_of_their_own(served):
    """No device program for a split-0 frame, no head-only cloud program for
    a device-only one: one device program per device-only schedule and
    cloud_embed programs for the rest."""
    rt, n = served["rt"], served["cfg"].n_layers
    device_only = _by_split(served)[rt.engines[0].profile.n_layers + 1]
    by_kind = rt.plan_cache.traces_by_kind
    assert set(by_kind) == {"device", "cloud_embed"}
    assert by_kind["device"] == len({_exec_geometry(rt, f)
                                     for f in device_only})
    assert all(_exec_geometry(rt, f)[1] == n + 1 for f in device_only)


def test_folded_counts_every_folded_frame(served):
    by_split = _by_split(served)
    n = served["rt"].engines[0].profile.n_layers
    assert served["folded_on"] == {
        "embed": len(by_split[0]), "head": len(by_split[n + 1])}


# ------------------------------------------------- programs, outside a fleet

@pytest.fixture(scope="module")
def small():
    args = serve.build_parser().parse_args(ARGV)
    _, _, cfg, params, images = serve.setup(args)
    sched = tuple(pruning.make_schedule("exponential", 0.27, cfg.n_layers,
                                        cfg.num_tokens))
    return cfg, params, images, sched


def _program(small, kind):
    """A cache's ``kind`` program and its arguments, first called once so
    that the cache holds the bare jitted function."""
    cfg, params, images, sched = small
    cache = engine.CompiledPlanCache()
    if kind == "device":
        args = (params, images)
        get = lambda: cache.device_fn(cfg, sched, cfg.n_layers, images)  # noqa: E731
    elif kind == "cloud_embed":
        args = (params, images)
        get = lambda: cache.cloud_embed_fn(cfg, sched, images)  # noqa: E731
    else:
        x, sizes = engine.device_forward(params, cfg, images, sched, 0)
        args = (params, x, sizes)
        if kind == "cloud":
            get = lambda: cache.cloud_fn(cfg, sched, 0, x)  # noqa: E731
        else:
            x, sizes = engine._pad_tokens(x, sizes, x.shape[1] + 3)
            args = (params, x, sizes)
            get = lambda: cache.cloud_padded_fn(cfg, sched, 0, x)  # noqa: E731
    get()(*args)
    return get(), args, cache


@pytest.mark.parametrize("kind", ["device", "cloud", "cloud_padded",
                                  "cloud_embed"])
def test_program_is_named(small, kind):
    fn, args, _ = _program(small, kind)
    assert f"module @jit_janus_{kind} " in fn.lower(*args).as_text()


@pytest.mark.parametrize("kind", ["device", "cloud_padded", "cloud_embed"])
def test_merge_ops_carry_the_tome_merge_scope(small, kind):
    """At alpha > 0 the merge's ops carry ``tome_merge`` in their name stack
    (the ``op_name`` metadata a device trace shows); the head does not."""
    fn, args, _ = _program(small, kind)
    text = fn.lower(*args).as_text(debug_info=True)
    assert "tome_merge" in text
    assert sum(small[3]) > 0


def test_build_s_rises_on_a_miss_only(small):
    cfg, params, images, sched = small
    cache = engine.CompiledPlanCache()
    fn = cache.device_fn(cfg, sched, 2, images)
    assert cache.build_s == 0.0
    jax.block_until_ready(fn(params, images))
    built = cache.build_s
    assert built > 0.0 and cache.misses == 1
    again = cache.device_fn(cfg, sched, 2, images)
    assert hasattr(again, "lower"), "a hit returns the bare jitted function"
    jax.block_until_ready(again(params, images))
    assert cache.build_s == built and cache.hits == 1
    other = cache.device_fn(cfg, sched, 3, images)
    other(params, images)
    assert cache.build_s > built and cache.misses == 2


def test_span_formats_args_only_under_the_profiler(tmp_path):
    calls = []

    def ids():
        calls.append(1)
        return "0:0"

    with spans.span(spans.CLOUD_BATCH, ids=ids):
        pass
    assert not calls
    with jax.profiler.trace(str(tmp_path)):
        with spans.span(spans.CLOUD_BATCH, frames=1, ids=ids):
            jnp.zeros(2).block_until_ready()
    assert calls == [1]
    assert host_spans(tmp_path) == [(spans.CLOUD_BATCH,
                                     {"frames": "1", "ids": "0:0"})]
