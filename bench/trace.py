"""Reduce a JAX profiler trace to device busy and idle time.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes. Device
planes are named ``/device:TPU:<n>``; their op events lie on the ``XLA Ops``
line and whole programs on ``XLA Modules``. The benchmark's own host spans
(``jax.profiler.TraceAnnotation``) lie on the host plane's threads.

``reduce`` takes plain event tuples, so that a test can feed it a
synthetic trace; ``load`` reads them from a file.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Events:
    """Events in seconds on the profiler's clock: per device, its ops and
    its programs as (name, start, end); host spans as (name, start, end)."""
    ops: dict[str, list[tuple[str, float, float]]]
    modules: dict[str, list[tuple[str, float, float]]]
    spans: list[tuple[str, float, float]]


@dataclasses.dataclass
class Reduction:
    window_s: float              # first to last edge of the window span
    busy_s: float                # union of op intervals, mean over devices
    device_ops: list[tuple[str, float]]    # op name, seconds summed
    idle_gaps: list[tuple[str, float]]     # gap named by host span, seconds
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(trace_dir: str | pathlib.Path, span_names: tuple[str, ...]) -> Events:
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                out = (ops if line.name == OPS_LINE else modules)[plane.name]
            elif not device:
                out = None
            else:
                continue
            for e in line.events:
                # an op event's name is its HLO text: keep the name before " = "
                ev = (e.name.split(" = ", 1)[0], e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                if out is not None:
                    out.append(ev)
                elif e.name in span_names:
                    spans.append(ev)
    return Events(dict(ops), dict(modules), spans)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping [start, end) intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _innermost(spans, t: float) -> str:
    """Name of the shortest host span that holds time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside spans"


def reduce(ev: Events, window_span: str, top: int = 10) -> Reduction:
    """Busy and idle time of every device inside the window that the host
    spans named ``window_span`` cover, from the first one's start to the
    last one's end. Each idle gap is named by the innermost host span at its
    middle and by the program that ends it."""
    win = [(s, e) for name, s, e in ev.spans if name == window_span]
    if not win or not ev.ops:
        raise ValueError(f"trace holds no {window_span!r} span or no device ops")
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    busy, by_op, gaps = 0.0, defaultdict(float), []
    for dev, dev_ops in ev.ops.items():
        clipped = _clip([(s, e) for _, s, e in dev_ops], lo, hi)
        merged = union(clipped)
        busy += sum(e - s for s, e in merged)
        for name, s, e in dev_ops:
            if e > lo and s < hi:
                by_op[name] += min(e, hi) - max(s, lo)
        mods = sorted(ev.modules.get(dev, []), key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            i = bisect.bisect_left(mod_starts, g1 - 1e-9)
            nxt = mods[i][0] if i < len(mods) else "end of window"
            gaps.append((f"{_innermost(ev.spans, (g0 + g1) / 2)} -> {nxt}", g1 - g0))
    n = len(ev.ops)
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return Reduction(hi - lo, busy / n, [(k, v / n) for k, v in ops], gaps, n)
