#!/usr/bin/env python3
"""Where a cell's time goes, by the program's own spans: one run of a cell
as ``bench/run.py`` makes it, read through the ``janus/`` spans.

  python3 bench/span_report.py --workload vit-l384.4g-pruned --seed 7 --seconds 30

Set-up (weights, runtime, two warm-up ticks) is split by ``jax.monitoring``
into tracing, lowering and compile or load, beside the program's
``CompiledPlanCache.build_s``. A window of ``--seconds`` runs with the
profiler off; then at least 3 ticks and 1 s run under the profiler, and the
trace is read: host time per tick by span, device idle time by the
innermost host span, the device time under the ``tome_merge`` scope and its
top ops, and whether each ``jit_janus_*`` program started on the device
after the span that dispatched it began. The per-layer readers of
``bench/metrics/`` are called on the same run, the ones that read these
spans included. It checks no answers (``bench/run.py`` does). Readings go
to earlier lines; the last line is one JSON object. ``--out DIR`` also
writes the reduced trace there as JSON. Without a TPU it exits non-zero.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402

READERS = ("host_ms_per_tick", "programs_per_frame", "cloud_batch_fill",
           "model_mfu", "device_idle_share", "planner_ms_per_tick",
           "device_dispatch_ms_per_tick", "cloud_dispatch_ms_per_tick",
           "sim_ms_per_tick", "merge_share", "setup_build_s")


def report(cell, seed: int, seconds: float, t_start: float, counter,
           device_kind: str, chips: int, out_dir=None) -> dict:
    import jax
    import flops
    import spans as sp
    import trace as trace_lib

    snap = counter.snapshot()
    sv = harness.build(cell, seed)
    rt, images = sv.rt, sv.images
    for i in range(harness.WARMUP_TICKS):
        _, fs = harness.tick(rt, images[i % len(images)])
    setup = counter.since(snap)
    build_s = getattr(rt.plan_cache, "build_s", None)
    setup_s = time.perf_counter() - t_start
    harness.log(f"set-up: setup_s={setup_s:.3f} build_s={build_s} {setup}")

    win = harness.measure(rt, images, seconds, seed)
    run = harness.Run(ticks=win.ticks, frames=win.frames, failed=win.failed,
                      window_s=win.seconds, programs=win.programs,
                      batch_sizes=win.batch_sizes, max_batch=sv.max_batch,
                      chips=chips, peak_flops=flops.peak(device_kind))
    lat = sorted(t.total_s for t in win.ticks)
    q = statistics.quantiles(lat, n=20) if len(lat) > 1 else [lat[0]] * 19
    e2e = {"frames_per_s": (win.frames - win.failed) / win.seconds,
           "tick_p95_ms": 1e3 * q[18], "setup_s": setup_s}

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        traced, t_tr = [], time.perf_counter()
        while len(traced) < harness.TRACE_MIN_TICKS or \
                time.perf_counter() - t_tr < harness.TRACE_MIN_S:
            traced.append(harness.tick(rt, images[len(traced) % len(images)])[1])
        span_us_on = _span_cost_us()
        jax.profiler.stop_trace()
        run.trace_flops = sum(harness.tick_flops(cell, f) for f in traced)
        ev, scoped = sp.load(d, harness.SPANS + sp.NAMES)
    span_us_off = _span_cost_us()
    n_ticks = len(traced)
    del traced
    run.trace = trace_lib.reduce(ev, "tick")
    tick_spans = [(s, e) for n, s, e in ev.spans if n == "tick"]
    lo, hi = min(s for s, _ in tick_spans), max(e for _, e in tick_spans)
    run.spans = ev.spans
    # no op has an op_name where the trace holds no program's HLO
    run.merge_s = sp.op_time(scoped, lo, hi, sp.MERGE_SCOPE) if scoped.programs else None
    run.op_s = sp.op_time(scoped, lo, hi)
    run.build_s = build_s

    metrics = {}
    for name in READERS:
        v = harness.reader(name).read(run)
        if v is not None:
            metrics[name] = v
    by_span = {n: 1e3 * (sp.per_tick(ev.spans, n) or 0.0)
               for n in sp.NAMES + ("rt.run", "block")}
    lags = sp.dispatch_lags(ev)
    idle = sp.idle_by_span(ev)
    rt_runs = [e - s for n, s, e in ev.spans if n == "rt.run"]
    out = {
        "e2e": e2e, "metrics": metrics, "setup": setup, "build_s": build_s,
        "traced_ticks": n_ticks, "window_s": run.trace.window_s,
        "busy_s": run.trace.busy_s,
        "ms_per_tick_by_span": by_span,
        "spans_per_tick": sp.count_per_tick(ev.spans, sp.NAMES),
        "spans_per_tick_by_name": {n: sp.count_per_tick(ev.spans, (n,))
                                   for n in sp.NAMES},
        "span_cost_us": {"profiler_on": span_us_on, "profiler_off": span_us_off},
        "traced_rt_run_ms": 1e3 * statistics.mean(rt_runs) if rt_runs else None,
        "untraced_host_ms_per_tick": 1e3 * statistics.mean(t.host_s for t in win.ticks),
        "idle_s_by_span": idle, "idle_s": sum(idle.values()),
        "dispatch": {"pairs": len(lags),
                     "started_after": sum(x >= 0 for x in lags),
                     "lag_min_s": min(lags, default=None),
                     "lag_median_s": statistics.median(lags) if lags else None},
        "merge_s": run.merge_s, "op_s": run.op_s,
        "merge_top_ops": sp.top_ops(scoped, lo, hi, sp.MERGE_SCOPE),
        "ops_with_op_name": sum(1 for ops in scoped.ops.values()
                                for _, path, _, _ in ops if path),
        "ops": sum(len(ops) for ops in scoped.ops.values()),
        "hlo_programs": scoped.programs,
        "decisions": {f"{a},{s}": n for (a, s), n in sorted(Counter(
            (round(f.alpha, 2), f.split) for f in fs.all_frames).items())},
    }
    if out_dir is not None:
        _write(pathlib.Path(out_dir), ev, scoped, lo, hi, out)
    return out


def _span_cost_us(n: int = 20000) -> float:
    """Wall microseconds of one empty span with an arg, as the program
    writes them (a name outside the ``janus/`` set, so no reader counts
    it)."""
    from jax.profiler import TraceAnnotation
    t0 = time.perf_counter()
    for i in range(n):
        with TraceAnnotation("span_cost", frame=i) if TraceAnnotation.is_enabled() \
                else TraceAnnotation("span_cost"):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


def _write(out_dir: pathlib.Path, ev, scoped, lo, hi, out: dict) -> None:
    """The reduced trace: host spans, programs, busy intervals and ops
    summed by (name, op_name), enough to redo every number above."""
    import trace as trace_lib
    by_op = defaultdict(lambda: [0.0, 0])
    for ops in scoped.ops.values():
        for name, path, s, e in ops:
            if e > lo and s < hi:
                by_op[(name, path)][0] += min(e, hi) - max(s, lo)
                by_op[(name, path)][1] += 1
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / "reduced_trace.json").write_text(json.dumps({
        "report": out, "spans": ev.spans,
        "modules": {d: [m for m in ms if m[2] > lo and m[1] < hi]
                    for d, ms in ev.modules.items()},
        "busy": {d: trace_lib.union(trace_lib._clip(
            [(s, e) for _, s, e in ops], lo, hi)) for d, ops in ev.ops.items()},
        "ops": sorted(([n, p, t, c] for (n, p), (t, c) in by_op.items()),
                      key=lambda r: -r[2])}))


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="The spans of one run of one cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="directory for the reduced trace")
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX's first device is {devices[0].platform}",
              file=sys.stderr)
        return 1
    harness.enable_compile_cache()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # a cache the machine keeps from one run to the next
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
    from compile_phases import PhaseCounter
    out = report(cell, args.seed, args.seconds, T_START, PhaseCounter(),
                 devices[0].device_kind, cell.chips, args.out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
