"""Where program builds spend their wall time, by ``jax.monitoring``:
tracing Python to jaxprs, lowering jaxprs to MLIR, and the backend's
compile or load from the persistent cache. Each phase is the union of its
intervals, so a jit traced inside another's trace counts once."""
from __future__ import annotations

import time

import jax

import trace as trace_lib
from compile_counter import CompileCounter

PHASES = {"trace_s": "/jax/core/compile/jaxpr_trace_duration",
          "lower_s": "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "backend_s": "/jax/core/compile/backend_compile_duration"}


class PhaseCounter(CompileCounter):
    """``CompileCounter`` whose ``since`` adds the seconds of each phase."""

    def __init__(self):
        super().__init__()
        self.intervals: dict[str, list[tuple[float, float]]] = {k: [] for k in PHASES}
        self._phase = {v: k for k, v in PHASES.items()}
        jax.monitoring.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **_):
        if event in self._phase:
            self.intervals[self._phase[event]].append((start, end))

    def snapshot(self) -> tuple:
        # jax.monitoring stamps its spans with time.time()
        return super().snapshot() + (time.time(),)

    def since(self, snap: tuple) -> dict:
        out = super().since(snap[:3])
        t0 = snap[3] if len(snap) > 3 else float("-inf")
        all_ivs = []
        for k, ivs in self.intervals.items():
            ivs = trace_lib.union([iv for iv in ivs if iv[0] >= t0])
            out[k] = sum(e - s for s, e in ivs)
            all_ivs += ivs
        out["any_phase_s"] = sum(e - s for s, e in trace_lib.union(all_ivs))
        return out
