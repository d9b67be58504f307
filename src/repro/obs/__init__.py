"""`repro.obs` — observability for planned execution.

Three layers, one loop:

- :mod:`repro.obs.trace` — :func:`~repro.obs.trace.span`, the one span API
  (a named interval on the profiler's clock, recorded also in a tracer when
  given), and a lightweight span recorder both executors emit per-op spans
  into; exports Chrome/Perfetto ``trace.json`` and the
  :meth:`~repro.plan.MemoryPlan.timeline` schema so predicted and measured
  timelines render side by side.
- :mod:`repro.obs.metrics` — a process-wide counters/gauges/histograms
  registry (JSON snapshot) wired into the hot seams: solver-cache
  hits/misses/evictions, DP fill wall time per impl, autotuner calibration
  decisions, host-buffer pin-pool occupancy, offload stall time, train-loop
  step time/loss, serving KV residency.
- :mod:`repro.obs.drift` — compare a plan's simulator-predicted
  makespan/peaks/stall against a measured trace, report per-layer drift,
  and feed measured per-layer times back into the chain cost model
  (:meth:`Chain.calibrate <repro.core.chain.Chain.calibrate>` → re-plan →
  convergence).

Everything here is stdlib + numpy only at import time (jax is touched
lazily, to open profiler annotations and to fence traced ops), so the
numpy core can report without dragging in an accelerator runtime.
"""

from . import metrics
from .drift import DriftReport, LayerDrift, calibrate_from_trace, compare
from .trace import (
    Span,
    Tracer,
    measured_stage_times,
    span,
    validate_perfetto,
    validate_trace_file,
)

__all__ = [
    "metrics",
    "Span",
    "Tracer",
    "measured_stage_times",
    "span",
    "validate_perfetto",
    "validate_trace_file",
    "DriftReport",
    "LayerDrift",
    "compare",
    "calibrate_from_trace",
]
