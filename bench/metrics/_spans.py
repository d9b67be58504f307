"""Arithmetic shared by the readers of the program's own spans and gauges.

Spans are the host events of a ``--trace 1`` run that the program names
(``repro.obs.trace.span``), read from the run's extracted trace and kept
where they lie inside the benchmark's window.  Gauges are the program's
``repro.obs.metrics`` registry in this process.  A program without them
reads ``None``, never 0.
"""

from __future__ import annotations

import bisect
import math
import statistics
from typing import List, Optional, Tuple

from bench.trace import window_of


def spans(run: dict, name: str) -> List[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of each host span ``name`` that lies inside
    the traced window, in order of start."""
    ex = run.get("trace_events")
    window = window_of(ex) if ex else None
    if window is None:
        return []
    lo, hi = window
    return sorted((s, s + d) for s, d, n in ex["host"]
                  if n == name and s >= lo and s + d <= hi)


def median_ms(ns: List[float]) -> Optional[float]:
    """Median of nanosecond readings, in milliseconds."""
    return 1e-6 * statistics.median(ns) if ns else None


def host_gaps_ns(run: dict, after: str, until: str) -> List[float]:
    """For each span ``until`` in the window that follows a span ``after``:
    its end minus the end of the latest ``after`` that ended before it
    started."""
    ends = [e for _, e in spans(run, after)]
    ends.sort()
    gaps = []
    for s, e in spans(run, until):
        i = bisect.bisect_right(ends, s)
        if i:
            gaps.append(e - ends[i - 1])
    return gaps


def gauge(name: str) -> Optional[float]:
    """The program's gauge ``name``, or None where it was never set (or is
    not a finite number)."""
    from repro.obs import metrics

    g = metrics.registry().get(name)
    if g is None or not getattr(g, "updates", 0):
        return None
    v = float(g.value)
    return v if math.isfinite(v) else None


def relative_error_pct(measured: Optional[float],
                       predicted: Optional[float]) -> Optional[float]:
    """100 × |measured − predicted| / measured."""
    if measured is None or predicted is None or not measured > 0:
        return None
    return 100.0 * abs(measured - predicted) / measured
