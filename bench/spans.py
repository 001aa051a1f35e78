"""The program's ``janus/`` spans and its ``tome_merge`` ops in a profiler
trace, reduced to per-layer numbers of the served path.

The program writes its spans with ``jax.profiler.TraceAnnotation``
(``src/repro/runtime/spans.py``), so they lie on the host plane on the
profiler's clock, beside the device's ops and programs. Their names are
the program's side of the contract and are listed here, so that the
benchmark imports nothing of the program: a program that writes none of
them (one older than the spans) leaves every number below empty.

Each HLO instruction carries ``op_name`` metadata: the jitted function's
name stack (``jit(janus_cloud_padded)/.../tome_merge/...``; a fusion takes
its root's). A device op's event names its instruction, without the
metadata; the ``XLA Modules`` event around it names its program, whose HLO
the trace keeps (``xspace.py``). The merge's share of device time sums the
ops whose ``op_name`` holds the ``tome_merge`` scope.

Every function takes plain tuples, so a test can feed it a synthetic trace;
``load`` reads them from a file.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
from collections import defaultdict

import trace as trace_lib
import xspace

PLAN_FRAME = "janus/plan_frame"
DECIDE = "janus/decide"
DEVICE_PARTITION = "janus/device_partition"
CODEC_ENCODE = "janus/codec/encode"
CODEC_DECODE = "janus/codec/decode"
CLOUD_BATCH = "janus/cloud_batch"
CLOUD_ASSEMBLE = "janus/cloud_batch/assemble"
CLOUD_CALL = "janus/cloud_batch/call"
PROGRAM_BUILD = "janus/program_build"
NAMES = (PLAN_FRAME, DECIDE, DEVICE_PARTITION, CODEC_ENCODE, CODEC_DECODE,
         CLOUD_BATCH, CLOUD_ASSEMBLE, CLOUD_CALL, PROGRAM_BUILD)
# the host's layers inside rt.run; what they leave is the simulator's
LAYERS = (DECIDE, DEVICE_PARTITION, CLOUD_BATCH)
# each of these spans dispatches one partition program
DISPATCH = (DEVICE_PARTITION, CLOUD_CALL)
PROGRAM_PREFIX = "jit_janus_"
MERGE_SCOPE = "tome_merge"


@dataclasses.dataclass
class Scoped:
    """Device ops as (name, op_name, start, end) per device, in seconds."""
    ops: dict[str, list[tuple[str, str, float, float]]]
    programs: int = 0        # programs whose HLO the trace holds


def load(trace_dir: str | pathlib.Path,
         span_names: tuple[str, ...]) -> tuple[trace_lib.Events, Scoped]:
    """``trace.load``'s events, with host spans named in ``span_names``
    (the exact name: a span's args are stats, never part of it), and each
    device op's ``op_name`` ('' where the trace holds no HLO for it)."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in pd.planes:
        device = plane.name.startswith(trace_lib.DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (trace_lib.OPS_LINE, trace_lib.MODULES_LINE):
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0]
                s, t = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
                if not device:
                    if e.name in span_names:
                        spans.append((name, s, t))
                elif line.name == trace_lib.MODULES_LINE:
                    modules[plane.name].append((name, s, t))
                else:
                    ops[plane.name].append((name, s, t))
    programs = xspace.program_op_names(trace_dir)
    scoped = {dev: by_program(dev_ops, modules.get(dev, []), programs)
              for dev, dev_ops in ops.items()}
    return (trace_lib.Events(dict(ops), dict(modules), spans),
            Scoped(scoped, len(programs)))


def by_program(ops, modules, programs: dict[int, dict[str, str]]):
    """``ops`` as (name, op_name, start, end): each op's op_name looked up
    in the HLO of the program whose module event holds the op's start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in ops:
        path = ""
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < mods[i][2]:
            pid = xspace.program_id(mods[i][0])
            path = programs.get(pid, {}).get(name.lstrip("%"), "")
        out.append((name, path, s, e))
    return out


# -- host spans ---------------------------------------------------------------------

def _within(spans, name, lo, hi):
    return [(s, e) for n, s, e in spans if n == name and lo <= s < hi]


def per_tick(spans, name: str, window: str = "rt.run") -> float | None:
    """Seconds of ``name`` spans that start inside each ``window`` span,
    summed, mean over the windows. None where the trace has no ``name``."""
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins or not any(n == name for n, _, _ in spans):
        return None
    return sum(e - s for lo, hi in wins
               for s, e in _within(spans, name, lo, hi)) / len(wins)


def outside(spans, names: tuple[str, ...], window: str = "rt.run") -> float | None:
    """Seconds of each ``window`` span that no span named in ``names``
    covers (nested spans count once), mean over the windows."""
    wins = [(s, e) for n, s, e in spans if n == window]
    if not wins or not any(n in names for n, _, _ in spans):
        return None
    total = 0.0
    for lo, hi in wins:
        inner = [(s, e) for n, s, e in spans if n in names and e > lo and s < hi]
        covered = trace_lib.union(trace_lib._clip(inner, lo, hi))
        total += (hi - lo) - sum(e - s for s, e in covered)
    return total / len(wins)


def count_per_tick(spans, names: tuple[str, ...], window: str = "rt.run") -> float:
    wins = [(s, e) for n, s, e in spans if n == window]
    n = sum(1 for nm, s, _ in spans if nm in names
            and any(lo <= s < hi for lo, hi in wins))
    return n / len(wins) if wins else 0.0


# -- device ops -----------------------------------------------------------------------

def op_time(scoped: Scoped, lo: float, hi: float,
            scope: str | None = None) -> float:
    """Seconds of device op time inside [lo, hi), summed over devices, of
    every op or of those whose op_name holds ``scope`` as a path element."""
    total = 0.0
    for dev_ops in scoped.ops.values():
        for _, path, s, e in dev_ops:
            if e > lo and s < hi and (scope is None or in_scope(path, scope)):
                total += min(e, hi) - max(s, lo)
    return total


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def top_ops(scoped: Scoped, lo: float, hi: float, scope: str,
            k: int = 5) -> list[tuple[str, str, float]]:
    """The ``k`` ops under ``scope`` that took longest inside [lo, hi), as
    (name, op_name, seconds): programs reuse op names, the pair tells them
    apart."""
    by = defaultdict(float)
    for dev_ops in scoped.ops.values():
        for name, path, s, e in dev_ops:
            if e > lo and s < hi and in_scope(path, scope):
                by[(name, path)] += min(e, hi) - max(s, lo)
    return [(n, p, t) for (n, p), t in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


# -- host and device together --------------------------------------------------------

def idle_by_span(ev: trace_lib.Events, window: str = "tick") -> dict[str, float]:
    """Device idle seconds inside the ``window`` spans, mean over devices,
    by the innermost host span: each gap is cut at the host spans' edges
    and each piece goes to the span that holds its middle."""
    win = [(s, e) for n, s, e in ev.spans if n == window]
    if not win or not ev.ops:
        return {}
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    cuts = sorted({t for _, s, e in ev.spans for t in (s, e) if lo < t < hi})
    out = defaultdict(float)
    for dev_ops in ev.ops.values():
        busy = trace_lib.union(trace_lib._clip([(s, e) for _, s, e in dev_ops], lo, hi))
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            i, j = bisect.bisect_right(cuts, g0), bisect.bisect_left(cuts, g1)
            pts = [g0] + cuts[i:j] + [g1]
            for a, b in zip(pts, pts[1:]):
                out[trace_lib._innermost(ev.spans, (a + b) / 2)] += b - a
    return {k: v / len(ev.ops) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def dispatch_lags(ev: trace_lib.Events, window: str = "tick") -> list[float]:
    """Seconds from the start of each dispatching span to the start of the
    ``jit_janus_*`` program it sent, inside the ``window`` spans, on each
    device: the k-th such program on a device is the one the k-th
    dispatching span sent, since a device runs its programs in the order
    the host dispatched them. On one clock none is negative."""
    win = [(s, e) for n, s, e in ev.spans if n == window]
    if not win:
        return []
    lo, hi = min(s for s, _ in win), max(e for _, e in win)
    disp = sorted(s for n, s, _ in ev.spans if n in DISPATCH and lo <= s < hi)
    lags = []
    for mods in ev.modules.values():
        starts = sorted(s for n, s, _ in mods
                        if n.startswith(PROGRAM_PREFIX) and lo <= s < hi)
        lags += [m - d for m, d in zip(starts, disp)]
    return lags
