"""Engine device partitions (``JanusEngine.execute_device``): wall time in
``janus/device_partition`` spans, program lookup and dispatch, per traced
tick, on the profiler's clock (ms)."""
import spans


def read(run):
    s = getattr(run, "spans", None)
    v = spans.per_tick(s, spans.DEVICE_PARTITION) if s else None
    return None if v is None else 1e3 * v
