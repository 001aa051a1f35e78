"""The span and merge readers on small synthetic traces, and the loader on
a real profiler trace of spans written the way the program writes them."""
import dataclasses

import jax
import pytest
from jax.profiler import TraceAnnotation

import compile_phases
import harness
import spans as sp
import test_trace
import trace as trace_lib
import xspace

# one traced tick of 10 s: rt.run 0-6, block 6-10. Inside rt.run: frame 0
# plans 0-3 (decide 0-1, device partition 1-2, then an inline head-only
# cloud batch 2-2.5 nested in its plan_frame), a micro-batch 4-5.5 (call
# 4.5-5). What no layer covers (2.5-4 and 5.5-6) is the simulator's: 2 s.
TICK = [("tick", 0.0, 10.0), ("rt.run", 0.0, 6.0), ("block", 6.0, 10.0)]
JANUS = [(sp.PLAN_FRAME, 0.0, 3.0), (sp.DECIDE, 0.0, 1.0),
         (sp.DEVICE_PARTITION, 1.0, 2.0), (sp.CLOUD_BATCH, 2.0, 2.5),
         (sp.CLOUD_CALL, 2.1, 2.4), (sp.CLOUD_BATCH, 4.0, 5.5),
         (sp.CLOUD_ASSEMBLE, 4.0, 4.5), (sp.CLOUD_CALL, 4.5, 5.0)]
# device: the device partition's program 1.5-2.2, the head 2.3-2.6, the
# micro-batch 4.8-7 (0.5 s of it a tome_merge fusion), idle otherwise
OPS = [("fusion.1", "jit_janus_device/blocks/mlp", 1.5, 2.2),
       ("fusion.2", "jit_janus_cloud/head", 2.3, 2.6),
       ("sort.3", "jit_janus_cloud_padded/block_0/tome_merge/sort", 4.8, 5.3),
       ("fusion.4", "jit_janus_cloud_padded/block_0/mlp", 5.3, 7.0)]
MODULES = [("jit_janus_device(1)", 1.5, 2.2), ("jit_janus_cloud(2)", 2.3, 2.6),
           ("jit_janus_cloud_padded(3)", 4.8, 7.0)]
DEV = "/device:TPU:0"


def events():
    return trace_lib.Events({DEV: [(n, s, e) for n, _, s, e in OPS]},
                            {DEV: MODULES}, TICK + JANUS)


def scoped():
    return sp.Scoped({DEV: OPS})


def traced_run(**extra):
    run = harness.Run(ticks=[harness.Tick(0.006, 0.010)], frames=2, failed=0,
                      window_s=0.01, programs=3, batch_sizes=[1], max_batch=8,
                      chips=1, peak_flops=1.0)
    for k, v in extra.items():
        setattr(run, k, v)
    return run


@pytest.mark.parametrize("metric, value", [
    ("planner_ms_per_tick", 1e3 * 1.0),
    ("device_dispatch_ms_per_tick", 1e3 * 1.0),
    ("cloud_dispatch_ms_per_tick", 1e3 * (0.5 + 1.5)),
    ("sim_ms_per_tick", 1e3 * 2.0),
])
def test_span_readers(metric, value):
    assert harness.reader(metric).read(traced_run(spans=TICK + JANUS)) == \
        pytest.approx(value)


@pytest.mark.parametrize("metric", ["planner_ms_per_tick", "device_dispatch_ms_per_tick",
                                    "cloud_dispatch_ms_per_tick", "sim_ms_per_tick",
                                    "merge_share", "setup_build_s"])
def test_readers_are_empty_without_the_program_s_spans(metric):
    """A program older than the spans: the harness's spans alone, no merge
    scope, no build counter. Nothing to read, and nothing raises."""
    assert harness.reader(metric).read(traced_run()) is None
    assert harness.reader(metric).read(traced_run(spans=TICK)) is None


def test_sim_counts_nested_spans_once():
    # the inline cloud batch 2-2.5 lies inside plan_frame, not inside the
    # decide or device spans; doubling it must not change the simulator's
    doubled = TICK + JANUS + [(sp.CLOUD_BATCH, 2.0, 2.5), (sp.DECIDE, 0.2, 0.8)]
    assert sp.outside(doubled, sp.LAYERS) == pytest.approx(2.0)
    assert sp.per_tick(TICK + JANUS, sp.CLOUD_BATCH) == pytest.approx(2.0)


def test_per_tick_is_a_mean_over_ticks():
    two = TICK + JANUS + [("rt.run", 20.0, 26.0), (sp.DECIDE, 20.0, 23.0)]
    assert sp.per_tick(two, sp.DECIDE) == pytest.approx((1.0 + 3.0) / 2)


def test_merge_share_and_top_ops():
    s = scoped()
    merge_s = sp.op_time(s, 0.0, 10.0, sp.MERGE_SCOPE)
    op_s = sp.op_time(s, 0.0, 10.0)
    assert (merge_s, op_s) == (pytest.approx(0.5), pytest.approx(3.2))
    run = traced_run(merge_s=merge_s, op_s=op_s)
    assert harness.reader("merge_share").read(run) == pytest.approx(100 * 0.5 / 3.2)
    assert sp.top_ops(s, 0.0, 10.0, sp.MERGE_SCOPE) == [
        ("sort.3", "jit_janus_cloud_padded/block_0/tome_merge/sort", pytest.approx(0.5))]
    # a scope is a whole path element, not a substring of one
    assert not sp.in_scope("jit_janus_cloud/tome_merge_like/x", sp.MERGE_SCOPE)


def test_setup_build_s_reads_the_counter():
    assert harness.reader("setup_build_s").read(traced_run(build_s=61.5)) == 61.5


def test_idle_gaps_are_named_by_the_innermost_janus_span():
    r = trace_lib.reduce(events(), "tick")
    assert r.busy_s == pytest.approx(0.7 + 0.3 + 2.2)
    # each gap is named by the innermost span at its middle
    assert dict(r.idle_gaps) == pytest.approx({
        "janus/decide -> jit_janus_device(1)": 1.5,
        "janus/cloud_batch/call -> jit_janus_cloud(2)": 0.1,
        "rt.run -> jit_janus_cloud_padded(3)": 2.2,
        "block -> end of window": 3.0})


def test_idle_by_span_cuts_gaps_at_span_edges():
    idle = sp.idle_by_span(events())
    # gap 0-1.5: 1 s in decide, 0.5 s in the device partition; gap 2.2-2.3
    # in the inline call; gap 2.6-4.8: 2.6-3 in plan_frame, 3-4 in rt.run
    # alone, 4-4.5 in assemble, 4.5-4.8 in the call; gap 7-10 in block
    assert idle == pytest.approx({
        sp.DECIDE: 1.0, sp.DEVICE_PARTITION: 0.5, sp.PLAN_FRAME: 0.4,
        "rt.run": 1.0, sp.CLOUD_ASSEMBLE: 0.5, sp.CLOUD_CALL: 0.1 + 0.3,
        "block": 3.0})
    assert sum(idle.values()) == pytest.approx(10.0 - 3.2)


def test_dispatch_lags():
    # dispatching spans start at 1.0, 2.1 and 4.5; their programs at 1.5,
    # 2.3 and 4.8
    assert sp.dispatch_lags(events()) == pytest.approx([0.5, 0.2, 0.3])
    early = dataclasses.replace(events(), modules={DEV: [
        ("jit_janus_device(1)", 0.5, 2.2)] + MODULES[1:]})
    assert sp.dispatch_lags(early) == pytest.approx([-0.5, 0.2, 0.3])
    # a program that ran before the window pairs with no span
    before = dataclasses.replace(events(), modules={DEV: [
        ("jit_janus_cloud(9)", -1.0, -0.5)] + MODULES})
    assert sp.dispatch_lags(before) == pytest.approx([0.5, 0.2, 0.3])


def test_the_existing_trace_reduces_as_before():
    """``test_trace``'s synthetic trace with no janus span at all."""
    r = trace_lib.reduce(test_trace.events(), "tick")
    assert r.busy_s == pytest.approx(4.0) and r.idle_share == pytest.approx(0.6)
    assert sp.idle_by_span(test_trace.events()) == pytest.approx(
        {"rt.run": 2.0, "block": 4.0})


def test_load_keeps_exact_span_names(tmp_path):
    """A span's args are stats of the event: its name stays exact."""
    jax.numpy.zeros(4).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("tick"):
            with TraceAnnotation(sp.PLAN_FRAME, stream=3, frame=7):
                with TraceAnnotation(sp.DECIDE):
                    pass
            with TraceAnnotation("janus/not_a_span"):
                pass
    ev, scoped = sp.load(tmp_path, harness.SPANS + sp.NAMES)
    assert sorted(n for n, _, _ in ev.spans) == sorted(["tick", sp.PLAN_FRAME, sp.DECIDE])


def test_phase_counter_counts_nested_intervals_once():
    c = compile_phases.PhaseCounter()
    snap = c.snapshot()
    t = snap[3]
    c._span(compile_phases.PHASES["trace_s"], t + 1.0, t + 4.0)
    c._span(compile_phases.PHASES["trace_s"], t + 2.0, t + 3.0)   # nested jit
    c._span(compile_phases.PHASES["lower_s"], t + 4.0, t + 5.0)
    c._span(compile_phases.PHASES["trace_s"], t - 9.0, t - 8.0)   # before snap
    out = c.since(snap)
    assert out["trace_s"] == pytest.approx(3.0)
    assert out["lower_s"] == pytest.approx(1.0)
    assert out["any_phase_s"] == pytest.approx(4.0)
    assert {"programs", "cache_hits", "compiled", "seconds"} <= set(out)


# -- the programs' HLO kept in the trace's metadata plane ---------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, v):
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _merge_module_proto() -> bytes:
    """A compiled program's HLO module whose merge ops carry ``tome_merge``."""
    import jax.numpy as jnp
    from repro.core import tome
    x, sizes = jnp.ones((1, 9, 4)), jnp.ones((1, 9))
    fn = jax.jit(lambda x, s: tome.tome_merge_padded(x, x, s, 2))
    exe = fn.lower(x, sizes).compile().runtime_executable()
    return exe.hlo_modules()[0].as_serialized_hlo_module_proto()


def write_metadata_trace(tmp_path, program: int, module_proto: bytes):
    stat = _field(1, 7) + _field(6, _field(1, module_proto))       # XStat: the HloProto
    event_meta = _field(1, program) + _field(2, b"jit_merge") + _field(5, stat)
    stat_meta = _field(1, 7) + _field(2, xspace.HLO_PROTO_STAT.encode())
    plane = (_field(2, xspace.METADATA_PLANE.encode())
             + _field(4, _field(1, program) + _field(2, event_meta))
             + _field(5, _field(1, 7) + _field(2, stat_meta)))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_field(1, _field(2, b"/device:TPU:0")) + _field(1, plane))


def test_program_op_names_reads_the_metadata_plane(tmp_path):
    write_metadata_trace(tmp_path, 613803951957644064, _merge_module_proto())
    programs = xspace.program_op_names(tmp_path)
    assert list(programs) == [613803951957644064]
    names = programs[613803951957644064]
    merged = [i for i, path in names.items() if sp.in_scope(path, sp.MERGE_SCOPE)]
    assert merged, names


def test_ops_take_the_op_name_of_their_program():
    programs = {613803951957644064: {"sort.3": "jit(f)/tome_merge/sort", "fusion.4": "jit(f)/mlp"}}
    mods = [("jit_janus_cloud_padded(613803951957644064)", 4.8, 7.0)]
    ops = [("%sort.3", 4.8, 5.3), ("%fusion.4", 5.3, 7.0), ("%sort.3", 7.5, 8.0),
           ("%copy.1", 5.0, 5.1)]
    assert [p for _, p, _, _ in sp.by_program(ops, mods, programs)] == [
        "jit(f)/tome_merge/sort", "jit(f)/mlp", "", ""]
    assert xspace.program_id("jit_janus_device(-1)") is None
    assert xspace.program_id("jit_x(18446744073709551615)") == (1 << 64) - 1
