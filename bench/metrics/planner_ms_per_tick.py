"""Planner (``core/planner.py`` via ``JanusEngine._decide``): wall time in
``janus/decide`` spans per traced tick, on the profiler's clock (ms)."""
import spans


def read(run):
    s = getattr(run, "spans", None)
    v = spans.per_tick(s, spans.DECIDE) if s else None
    return None if v is None else 1e3 * v
