"""The trace reduction on a small synthetic trace."""
import pytest

import trace as trace_lib


def events():
    # one window span of 10 s; device busy 1-3 and 2-4 (overlap) and 6-7;
    # the host is in rt.run for 0-5 and in block for 5-10
    ops = {"/device:TPU:0": [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0),
                             ("convolution", 6.0, 7.0), ("late", 11.0, 12.0)]}
    modules = {"/device:TPU:0": [("jit_a", 1.0, 4.0), ("jit_b", 6.0, 7.0)]}
    spans = [("tick", 0.0, 10.0), ("rt.run", 0.0, 5.0), ("block", 5.0, 10.0)]
    return trace_lib.Events(ops, modules, spans)


def test_union_merges_overlaps():
    assert trace_lib.union([(2, 4), (1, 3), (6, 7)]) == [(1, 4), (6, 7)]


def test_busy_idle_and_gaps():
    r = trace_lib.reduce(events(), "tick")
    assert r.window_s == pytest.approx(10.0)
    assert r.busy_s == pytest.approx(4.0)          # [1, 4] and [6, 7]
    assert r.idle_share == pytest.approx(0.6)
    assert dict(r.device_ops) == pytest.approx(
        {"fusion.1": 2.0, "fusion.2": 2.0, "convolution": 1.0})
    # gaps 0-1, 4-6 and 7-10, each named by the host span at its middle and
    # by the program that ends it
    assert r.idle_gaps == [("block -> end of window", pytest.approx(3.0)),
                           ("block -> jit_b", pytest.approx(2.0)),
                           ("rt.run -> jit_a", pytest.approx(1.0))]


def test_busy_is_averaged_over_devices():
    ev = events()
    ev.ops["/device:TPU:1"] = [("fusion.9", 0.0, 10.0)]
    r = trace_lib.reduce(ev, "tick")
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(7.0)


def test_no_window_is_an_error():
    ev = events()
    ev.spans = [s for s in ev.spans if s[0] != "tick"]
    with pytest.raises(ValueError):
        trace_lib.reduce(ev, "tick")
