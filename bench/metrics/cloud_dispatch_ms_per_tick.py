"""Engine cloud batch (``engine.run_cloud_batch``): wall time in
``janus/cloud_batch`` spans, assembly, program dispatch and the slicing of
each member's logits, per traced tick, on the profiler's clock (ms)."""
import spans


def read(run):
    s = getattr(run, "spans", None)
    v = spans.per_tick(s, spans.CLOUD_BATCH) if s else None
    return None if v is None else 1e3 * v
