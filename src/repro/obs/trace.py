"""Lightweight span recorder for planned execution, on the profiler's clock.

:func:`span` is the one way to time a block of host code: it opens a
``jax.profiler.TraceAnnotation`` under the span's name, so the block shows
in any profiler trace taken around it (``jax.profiler.trace``, Perfetto,
TensorBoard) on the same clock as the device's operations, and, given an
enabled :class:`Tracer`, also records the interval as a :class:`Span`.
With no profiler session and no tracer it costs about a microsecond.

Both executors — the op-faithful eager walker
(:func:`repro.offload.executor.execute_offload_schedule`, reached through
``core.executor.execute_schedule`` / ``plan.execute``) and the jitted
nested-remat binding (:class:`repro.plan.plan.BoundPlan`, behind an opt-in
flag) — emit one :class:`Span` per schedule op into a :class:`Tracer`:
op kind (``Fall``/``Fck``/``Fnone``/``B``/``Foff``/``Prefetch``, plus
``Decode``/``Step`` from the serving loop and the ``train.*`` spans of the
train loop), op index, bytes moved/produced where cheap to know, and wall
time.

The recorder is deliberately dumb: ``record`` appends a dataclass to a
list.  All interpretation lives in the exporters —

- :meth:`Tracer.to_perfetto` — Chrome/Perfetto ``trace.json`` (the
  ``chrome://tracing`` / https://ui.perfetto.dev event format), one complete
  ``"X"`` event per span, one track per span category;
- :meth:`Tracer.to_timeline` — the :meth:`repro.plan.MemoryPlan.timeline`
  schema (``op``/``arg``/``t_start``/``t_end``/``device_mem``/``host_mem``)
  so a *measured* timeline renders side by side with the simulator's
  *predicted* one and feeds :mod:`repro.obs.drift` directly.

Timestamps are ``time.perf_counter`` seconds relative to the tracer's
epoch (its construction, or the first span).  ``sync=True`` (the default)
fences each traced op with ``jax.block_until_ready`` so a span's wall time
covers the op's real device work, not just its Python dispatch — this is
the opt-in cost of tracing; untraced runs are untouched.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence

#: span categories, used as Perfetto track (tid) names
CAT_FORWARD = "forward"
CAT_BACKWARD = "backward"
CAT_TRANSFER = "transfer"
CAT_STEP = "step"
CAT_DECODE = "decode"

#: track order in the Perfetto export ("misc" catches unknown op kinds)
_CATEGORIES = (CAT_FORWARD, CAT_BACKWARD, CAT_TRANSFER, CAT_STEP, CAT_DECODE)

_OP_CATEGORY = {
    "Fall": CAT_FORWARD,
    "Fck": CAT_FORWARD,
    "Fnone": CAT_FORWARD,
    "B": CAT_BACKWARD,
    "Foff": CAT_TRANSFER,
    "Prefetch": CAT_TRANSFER,
    "Step": CAT_STEP,
    "train.step": CAT_STEP,
    "Decode": CAT_DECODE,
}


def category_of(op: str) -> str:
    return _OP_CATEGORY.get(op, "misc")


@dataclasses.dataclass
class Span:
    """One timed operation: ``[t_start, t_end]`` in tracer-epoch seconds."""

    op: str  # op kind (Fall/Fck/Fnone/B/Foff/Prefetch/...)
    arg: Any  # op index (stage l or activation i)
    t_start: float
    t_end: float
    bytes: Optional[int] = None  # bytes produced/moved, when known
    device_mem: Optional[float] = None
    host_mem: Optional[float] = None
    extra: Optional[Dict[str, Any]] = None

    @property
    def duration(self) -> float:
        return self.t_end - self.t_start

    @property
    def category(self) -> str:
        return category_of(self.op)


class Tracer:
    """Append-only span recorder with Perfetto / timeline exporters.

    ``enabled=False`` makes every call a no-op (so call sites can thread one
    tracer object unconditionally); ``sync`` asks instrumented executors to
    fence each op with ``jax.block_until_ready`` before closing its span.
    """

    def __init__(self, enabled: bool = True, sync: bool = True, name: str = "repro"):
        self.enabled = enabled
        self.sync = sync
        self.name = name
        self.spans: List[Span] = []
        self._epoch = time.perf_counter()

    # -- recording ---------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter() - self._epoch

    def record(self, op: str, arg: Any, t_start: float, t_end: float, **kw) -> None:
        """Append a span with explicit epoch-relative times."""
        if not self.enabled:
            return
        self.spans.append(Span(op, arg, t_start, t_end, **kw))

    def span(self, op: str, arg: Any = None, **kw) -> "_Interval":
        """Context manager measuring the block as one span (see
        :func:`span`; ``kw`` are :class:`Span` fields)."""
        return span(op, self, arg=arg, **kw)

    def fence(self, value: Any) -> None:
        """Block on a jax value (when ``sync``), so the enclosing span's end
        time covers the device work.  Accepts arbitrary pytrees; silently
        skips non-jax values so CPU/numpy paths trace too."""
        if not (self.enabled and self.sync) or value is None:
            return
        try:
            import jax

            jax.block_until_ready(value)
        except Exception:
            pass

    def clear(self) -> None:
        self.spans.clear()
        self._epoch = time.perf_counter()

    def __len__(self) -> int:
        return len(self.spans)

    # -- exporters ---------------------------------------------------------

    @property
    def makespan(self) -> float:
        """Wall time covered by the recorded spans, in seconds."""
        if not self.spans:
            return 0.0
        t0 = min(s.t_start for s in self.spans)
        t1 = max(s.t_end for s in self.spans)
        return t1 - t0

    def to_perfetto(self) -> Dict[str, Any]:
        """Chrome trace-event JSON: one complete ("X") event per span, with
        microsecond timestamps, grouped into one named track per category."""
        tids = {}
        events: List[Dict[str, Any]] = []
        for cat in _CATEGORIES + ("misc",):
            tids[cat] = len(tids) + 1
        for cat, tid in tids.items():
            meta = {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid}
            meta["args"] = {"name": cat}
            events.append(meta)
        for s in self.spans:
            args: Dict[str, Any] = {"arg": s.arg}
            if s.bytes is not None:
                args["bytes"] = s.bytes
            if s.device_mem is not None:
                args["device_mem"] = s.device_mem
            if s.host_mem is not None:
                args["host_mem"] = s.host_mem
            if s.extra:
                args.update(s.extra)
            events.append(
                {
                    "name": f"{s.op}^{s.arg}" if s.arg is not None else s.op,
                    "cat": s.category,
                    "ph": "X",
                    "pid": 1,
                    "tid": tids.get(s.category, tids["misc"]),
                    "ts": s.t_start * 1e6,
                    "dur": max(s.duration, 0.0) * 1e6,
                    "args": args,
                }
            )
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {"tracer": self.name},
        }

    def to_timeline(self) -> List[Dict[str, Any]]:
        """The measured timeline in the exact
        :meth:`repro.plan.MemoryPlan.timeline` schema (memory fields are
        ``None`` unless the executor recorded them)."""
        rows = []
        for s in self.spans:
            rows.append(
                {
                    "op": s.op,
                    "arg": s.arg,
                    "t_start": s.t_start,
                    "t_end": s.t_end,
                    "device_mem": s.device_mem,
                    "host_mem": s.host_mem,
                }
            )
        return rows

    def save(self, path: str) -> None:
        """Write the Perfetto ``trace.json`` (load at ui.perfetto.dev)."""
        with open(path, "w") as f:
            json.dump(self.to_perfetto(), f)

    # -- constructors ------------------------------------------------------

    @staticmethod
    def from_timeline(
        rows: Iterable[Dict[str, Any]], name: str = "simulator"
    ) -> "Tracer":
        """A tracer replaying a predicted timeline
        (:meth:`repro.plan.MemoryPlan.timeline` rows) as spans — the bridge
        that lets :mod:`repro.obs.drift` compare simulator against
        simulator (zero drift by construction) or render a predicted
        timeline through the same Perfetto exporter."""
        tr = Tracer(name=name)
        for r in rows:
            tr.record(
                r["op"],
                r["arg"],
                float(r["t_start"]),
                float(r["t_end"]),
                device_mem=r.get("device_mem"),
                host_mem=r.get("host_mem"),
            )
        return tr


class _Interval:
    """An open :func:`span`: the profiler annotation, the start time, and
    what the tracer's :class:`Span` gets."""

    __slots__ = ("_name", "_tracer", "_arg", "_fields", "_ann", "_t0",
                 "seconds")

    def __init__(self, name: str, tracer: Optional[Tracer], arg: Any,
                 fields: Dict[str, Any], ann: Any):
        self._name = name
        self._tracer = tracer
        self._arg = arg
        self._fields = fields
        self._ann = ann
        self.seconds = None

    def note(self, **fields) -> None:
        """Set :class:`Span` fields known only at the block's end."""
        self._fields.update(fields)

    def __enter__(self) -> "_Interval":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.seconds = t1 - self._t0
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr.record(self._name, self._arg, self._t0 - tr._epoch,
                      t1 - tr._epoch, **self._fields)


def span(name: str, tracer: Optional[Tracer] = None, *,
         step: Optional[int] = None, arg: Any = None,
         marks_step: bool = False, **fields) -> _Interval:
    """Time the ``with`` block as one named interval on the profiler's clock.

    Opens ``jax.profiler.TraceAnnotation(name, step=step, arg=arg)`` (the
    arguments that are given; the profiler keeps them beside the name, so
    the event is named ``name`` exactly).  ``marks_step=True`` opens a
    ``StepTraceAnnotation(name, step_num=step)`` instead: the profiler's
    step marker.  When ``tracer`` is given and enabled, the interval is
    also recorded there as a :class:`Span` (``op=name``, ``arg`` or else
    ``step``, and ``fields``).  The object the block gets has ``note()``
    for fields known only at its end, and ``seconds`` once it closes.
    """
    from jax import profiler  # the module stays stdlib at import

    if marks_step:
        ann = profiler.StepTraceAnnotation(name, step_num=step)
    else:
        kw = {}
        if step is not None:
            kw["step"] = step
        if arg is not None:
            kw["arg"] = arg
        ann = profiler.TraceAnnotation(name, **kw)
    return _Interval(name, tracer, step if arg is None else arg, fields, ann)


# ---------------------------------------------------------------------------
# validation (CI artifact check + tests)
# ---------------------------------------------------------------------------


def validate_perfetto(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Validate a Perfetto trace document: returns the complete ("X")
    events, raising ``ValueError`` on an empty, malformed, or
    non-monotone trace.  Used by the CI smoke step and the schema tests."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("not a Chrome trace document (no traceEvents)")
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    if not events:
        raise ValueError("trace has no complete ('X') span events")
    last_ts = None
    for e in events:
        for key in ("name", "ts", "dur", "pid", "tid"):
            if key not in e:
                raise ValueError(f"span event missing {key!r}: {e}")
        ts, dur = float(e["ts"]), float(e["dur"])
        if dur < 0:
            raise ValueError(f"negative duration: {e}")
        if last_ts is not None and ts + 1e-9 < last_ts:
            raise ValueError(f"non-monotone span start: {ts} after {last_ts}")
        last_ts = ts
    return events


def validate_trace_file(path: str) -> int:
    """Validate a ``trace.json`` on disk; returns the span count."""
    with open(path) as f:
        doc = json.load(f)
    return len(validate_perfetto(doc))


# ---------------------------------------------------------------------------
# measured per-stage times (consumed by repro.obs.drift / Chain.calibrate)
# ---------------------------------------------------------------------------


def measured_stage_times(spans: Sequence[Span], length: int):
    """Aggregate spans into per-stage mean forward/backward wall times.

    Returns ``(uf, ub)`` — two float lists of length ``length + 1`` (stage
    ``l`` of the paper at index ``l - 1``, loss stage last), ``nan`` where
    the trace holds no sample — exactly the shape
    :meth:`repro.core.chain.Chain.calibrate` consumes.  Forward samples
    pool every execution of the stage (``Fall``/``Fck``/``Fnone``,
    recomputes included); backward samples come from ``B`` spans.
    """
    n = length + 1
    fwd_sum = [0.0] * n
    fwd_cnt = [0] * n
    bwd_sum = [0.0] * n
    bwd_cnt = [0] * n
    for s in spans:
        if s.op in ("Fall", "Fck", "Fnone"):
            stage = int(s.arg)
            if 1 <= stage <= n:
                fwd_sum[stage - 1] += s.duration
                fwd_cnt[stage - 1] += 1
        elif s.op == "B":
            stage = int(s.arg)
            if 1 <= stage <= n:
                bwd_sum[stage - 1] += s.duration
                bwd_cnt[stage - 1] += 1
    nan = float("nan")
    uf = [fwd_sum[i] / fwd_cnt[i] if fwd_cnt[i] else nan for i in range(n)]
    ub = [bwd_sum[i] / bwd_cnt[i] if bwd_cnt[i] else nan for i in range(n)]
    return uf, ub
