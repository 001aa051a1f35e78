"""Simulator and accounting (``serving/simcore.py``, ``fleet.py``,
``account_breakdown``): wall time of each traced tick's ``rt.run`` that no
``janus/decide``, ``janus/device_partition`` or ``janus/cloud_batch`` span
covers, nested spans counted once, mean over the traced ticks (ms)."""
import spans


def read(run):
    s = getattr(run, "spans", None)
    v = spans.outside(s, spans.LAYERS) if s else None
    return None if v is None else 1e3 * v
