"""Reduction of a JAX profiler trace to the device's busy and idle time,
the device operations that took most time, and the longest idle gaps with
what the host was doing in each.

``extract`` reads an ``.xplane.pb`` into plain lists (so that a recorded
trace can be kept as test data and reduced without JAX); ``reduce`` works
on those lists alone.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

#: Planes of the chips, the line of each that holds one event per device
#: operation, and the line that holds one per program execution.
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: The benchmark's own host span around the measured window.
WINDOW_SPAN = "bench.window"


#: The host event that hands a program to the chip; a program cannot start
#: on the device before it.
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[4,2048]{...} fusion(...)`` -> ``fusion.12
    bf16[4,2048]``: the instruction's name and its result's type."""
    lhs, _, rhs = hlo.partition(" = ")
    return (lhs.lstrip("%") + " " + rhs.split("{")[0].split(" ")[0]).strip()


def module_name(name: str) -> str:
    """``jit_train_step(8911407554505906894)`` -> ``jit_train_step``."""
    return name.split("(")[0]


def extract(path: str) -> dict:
    """``{"devices": {plane: [[start_ns, dur_ns, name], ...]},
    "modules": {plane: [...]}, "host": [[start_ns, dur_ns, name], ...]}``
    from a trace file: device operations, program executions, and host
    events with a duration."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                dest = {OPS_LINE: devices, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    name = op_name if line.name == OPS_LINE else module_name
                    dest.setdefault(plane.name, []).extend(
                        [float(e.start_ns), float(e.duration_ns), name(e.name)]
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([float(e.start_ns), float(e.duration_ns), e.name]
                            for e in line.events if e.duration_ns > 0)
    ex = {"devices": devices, "modules": modules, "host": host}
    return align(ex)


def align(ex: dict) -> dict:
    """Shift the device events onto the host's clock.  The profiler's device
    timestamps run early by a roughly constant offset (about a millisecond
    on a v5e); the shift is the least that puts no program's start before
    its launch on the host, pairing the first device's programs with the
    launches in order.  Left as they are when the counts differ."""
    launches = sorted(s for s, d, n in ex["host"] if n == LAUNCH)
    mods = ex.get("modules") or {}
    first = sorted(s for s, d, n in mods[sorted(mods)[0]]) if mods else []
    shift = 0.0
    if launches and len(launches) == len(first):
        shift = max(0.0, max(h - dv for h, dv in zip(launches, first)))
    if shift:
        for group in ("devices", "modules"):
            for evs in ex[group].values():
                for e in evs:
                    e[0] += shift
    ex["shift_ns"] = shift
    return ex


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def window_of(ex: dict) -> Optional[Tuple[float, float]]:
    """The benchmark's window span, ``(start_ns, end_ns)``, if traced."""
    spans = [(s, s + d) for s, d, n in ex["host"] if n == WINDOW_SPAN]
    return max(spans, key=lambda x: x[1] - x[0]) if spans else None


def _attribute(gap, host) -> str:
    """What the host was doing in ``gap``: the host event that overlaps it
    most (the window span itself left out), the shorter on a tie."""
    g0, g1 = gap
    best, key = "unattributed", (0.0, 0.0)
    for s, d, name in host:
        if name == WINDOW_SPAN:
            continue
        ov = min(g1, s + d) - max(g0, s)
        if ov > 0 and (ov, -d) > key:
            best, key = name, (ov, -d)
    return best


def reduce(ex: dict, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> dict:
    """Busy and idle time of the devices over ``window`` (default: the
    benchmark's window span), averaged over the devices that ran any
    operation; the ``top`` device operations by time (summed over devices
    and divided by their number) and the ``top`` longest idle gaps of the
    first device, each named by what the host was doing.  Returns None
    when no device ran an operation in the window."""
    window = window or window_of(ex)
    if window is None:
        return None
    lo, hi = window
    planes = {k: _clip([(s, s + d) for s, d, _ in v], lo, hi)
              for k, v in sorted(ex["devices"].items())}
    planes = {k: v for k, v in planes.items() if v}
    if not planes:
        return None
    busy = {k: union(v) for k, v in planes.items()}
    busy_ns = sum(sum(e - s for s, e in u) for u in busy.values()) / len(busy)
    per_op: Dict[str, float] = {}
    for k, evs in ex["devices"].items():
        if k not in planes:
            continue
        for s, d, name in evs:
            ov = min(hi, s + d) - max(lo, s)
            if ov > 0:
                per_op[name] = per_op.get(name, 0.0) + ov
    ops = sorted(((n, t / len(busy) / 1e9) for n, t in per_op.items()),
                 key=lambda x: -x[1])[:top]
    first = busy[next(iter(busy))]
    gaps, t = [], lo
    for s, e in first:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_attribute(g, ex["host"]), (g[1] - g[0]) / 1e9) for g in gaps[:top]]
    window_ns = hi - lo
    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_frac": 1.0 - busy_ns / window_ns, "devices": len(busy),
            "device_ops": [list(x) for x in ops],
            "idle_gaps": [list(x) for x in named]}


def module_runs(ex: dict, pred, window: Optional[Tuple[float, float]] = None
                ) -> Tuple[int, float]:
    """Program executions whose module name satisfies ``pred`` and that lie
    inside the window, on the first device: ``(count, seconds)``."""
    window = window or window_of(ex)
    mods = ex.get("modules") or {}
    if window is None or not mods:
        return 0, 0.0
    lo, hi = window
    evs = mods[sorted(mods)[0]]
    hits = [d for s, d, name in evs if pred(name) and s >= lo and s + d <= hi]
    return len(hits), sum(hits) / 1e9
