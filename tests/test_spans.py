"""The ``janus/`` spans and program names of the served path
(``repro.runtime.spans``, ``engine.CompiledPlanCache``, ``core.tome``).

A fleet of the ``--smoke-model`` stand-in runs under ``--execute`` once
with ``jax.profiler.trace`` around it (cold: every program is built then)
and once without. The trace's host plane must hold one span per frame and
per micro-batch with the args the docs promise, the programs must carry
stable ``jit_janus_*`` names and the ToMe merge its ``tome_merge`` scope,
and the profiler must change nothing that the run returns.
"""
import dataclasses
import pathlib
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, pruning
from repro.launch import serve
from repro.runtime import spans

# 4G/static on the paper's timing profile: most frames offload at alpha
# near 0.27 (ToMe merges in padded cloud micro-batches), some run
# device-only (their head-only cloud program runs inline)
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
    return rt, cfg, images


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    rt, cfg, images = _build()
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        fs_on = rt.run(images=images)
        jax.block_until_ready([f.logits for f in fs_on.all_frames])
    fs_off = rt.run(images=images)
    return dict(rt=rt, cfg=cfg, fs_on=fs_on, fs_off=fs_off,
                spans=host_spans(d))


def _frame_ids(fs) -> list[str]:
    return [f"{si}:{fi}" for si, st in enumerate(fs.per_stream)
            for fi in range(len(st.frames))]


@pytest.mark.parametrize("name", [spans.PLAN_FRAME, spans.DECIDE,
                                  spans.DEVICE_PARTITION])
def test_one_span_per_frame(served, name):
    n = sum(1 for nm, _ in served["spans"] if nm == name)
    assert n == len(served["fs_on"].all_frames)


def test_plan_frame_args_name_every_frame(served):
    got = sorted(f"{a['stream']}:{a['frame']}" for nm, a in served["spans"]
                 if nm == spans.PLAN_FRAME)
    assert got == sorted(_frame_ids(served["fs_on"]))


def test_device_partition_carries_the_split(served):
    got = Counter(int(a["split"]) for nm, a in served["spans"]
                  if nm == spans.DEVICE_PARTITION)
    assert got == Counter(f.split for f in served["fs_on"].all_frames)


def test_one_cloud_batch_span_per_micro_batch(served):
    """Every micro-batch is one ``janus/cloud_batch`` whose ``ids`` name its
    members; a device-only frame's head-only program is one more, inline."""
    fs, n = served["fs_on"], served["rt"].engines[0].profile.n_layers
    batches = [a for nm, a in served["spans"] if nm == spans.CLOUD_BATCH]
    device_only = [f for f in fs.all_frames if f.split == n + 1]
    assert device_only, "the scenario should hold device-only frames"
    assert sorted(int(a["frames"]) for a in batches) == \
        sorted(fs.batch_sizes + [1] * len(device_only))
    ids = sorted(i for a in batches for i in a["ids"].split(","))
    assert ids == sorted(_frame_ids(fs))


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


@pytest.mark.parametrize("kind", ["device", "cloud", "cloud_padded"])
def test_program_is_named(small, kind):
    fn, args, _ = _program(small, kind)
    assert f"module @jit_janus_{kind} " in fn.lower(*args).as_text()


@pytest.mark.parametrize("kind", ["device", "cloud_padded"])
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
