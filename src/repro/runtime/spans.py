"""Named spans of the Janus served path, on the profiler's clock.

Each span is a ``jax.profiler.TraceAnnotation`` (a TraceMe). With no
profiler running it costs about a microsecond and records nothing; under
``jax.profiler.trace`` it lands on the host plane of the trace, on the same
clock as the device's ops and programs. The profiler is both the switch and
the exporter. The names and what each span covers are listed in
``docs/observability.md``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

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


def span(name: str, **args) -> TraceAnnotation:
    """A span named ``name``. ``args`` become the event's stats, and are
    formatted only while a profiler records; a callable arg is called then
    too, so a costly one (a list of frame ids) costs nothing otherwise."""
    if args and TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **{k: v() if callable(v) else v
                                        for k, v in args.items()})
    return TraceAnnotation(name)
