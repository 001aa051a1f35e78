"""Model step: the operations the traced ticks' frames require (the
family's ``frame_flops``, from each frame's executed merge schedule, padding
not counted) over the traced window, the chips used and their bf16 peak (%)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace_flops / (run.trace.window_s * run.chips * run.peak_flops)
