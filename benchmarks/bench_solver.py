"""Paper §5.2: DP solver runtime vs chain length (their C implementation:
<1 s typical, ~20 s at L=339 / S=500).

Times the solver impls per chain length:

- **banded**         — the default two-tier DP on the split-batched float32
  band kernels (``repro.core.dp_kernels``), saturated m-columns pruned,
- **banded-noprune** — the same fill with ``REPRO_DP_PRUNE=0`` (the pruning
  delta is recorded as ``pruning_speedup`` on this row),
- **pallas**         — the per-band Pallas kernel (``repro.kernels.dp_fill``)
  behind ``impl="pallas"``; these are CPU rows, so ``run()`` runs it in
  Pallas interpret mode (and restores the setting) and times it only up to
  ``pallas_max_len`` — the row records the *seam*, not TPU speed,
- **pallas_fused**   — the device-resident fill behind ``impl="pallas_fused"``:
  the whole band recursion in ONE ``pallas_call`` (no per-band host loop) —
  CPU-capped at the same ``pallas_max_len`` for the same reason; the row's
  ``device_dispatches`` field records the kernel-launch count (asserted 1),
- **reference**      — the retained seed per-cell float64 fill (the ≥10×
  claim is measured against it),
- **offload**        — the three-tier DP (same kernels, one extra candidate
  plane) on the same chain priced with a host link.

Also reports ``Solution.table_bytes`` per impl (the banded layout must be
≥4× smaller) and the latency of a *second* identical solve, which is served
by the solver cache without any table fill.

``run()`` returns a machine-readable dict; ``benchmarks/run.py`` (and this
module's CLI) dump it to ``BENCH_solver.json`` so the perf trajectory is
tracked across PRs (``benchmarks/compare_trajectory.py`` gates CI on it).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import numpy as np

from repro.core.chain import Chain, HostTransferModel
from repro.core.schedule import Schedule, simulate
from repro.core.solver import solve_optimal
from repro.kernels.dp_fill.ops import interpreting
from repro.offload.solver import solve_optimal_offload

JSON_PATH = "BENCH_solver.json"

#: Interpret-mode Pallas executes kernel bodies in Python — fine for parity,
#: hopeless for timing big chains on CPU.  Lengths above this are skipped
#: (and logged).
PALLAS_MAX_LEN = 50


@contextlib.contextmanager
def _count_dispatches():
    """Counting shim on ``pallas_call`` (as seen by the dp_fill kernels):
    yields a one-element list incremented per device dispatch — how the
    single-dispatch claim of ``impl="pallas_fused"`` is recorded."""
    from repro.kernels.dp_fill import kernel as dpk

    calls = [0]
    orig = dpk.pl.pallas_call

    def counting(*args, **kwargs):
        calls[0] += 1
        return orig(*args, **kwargs)

    dpk.pl.pallas_call = counting
    try:
        yield calls
    finally:
        dpk.pl.pallas_call = orig


@contextlib.contextmanager
def _pruning_disabled():
    old = os.environ.get("REPRO_DP_PRUNE")
    os.environ["REPRO_DP_PRUNE"] = "0"
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_DP_PRUNE"]
        else:
            os.environ["REPRO_DP_PRUNE"] = old


def _chain(L: int, rng) -> Chain:
    n = L + 1
    return Chain.make(
        uf=rng.uniform(0.5, 2.0, n), ub=rng.uniform(1.0, 4.0, n),
        wa=rng.uniform(0.5, 2.0, n), wabar=rng.uniform(1.0, 4.0, n))


def _best_of(fn, repeats: int):
    best, out = None, None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best, out


# these are CPU rows: the Pallas fills interpret their kernels, and the
# previous setting comes back when run() returns
@interpreting()
def run(lengths=(20, 50, 100, 200, 339), num_slots=500, emit=print,
        reference=True, offload=True, repeats=2, pallas=True,
        pallas_max_len=PALLAS_MAX_LEN, prune_rows=True):
    emit("L,num_slots,impl,solve_s,feasible,expected_time,table_bytes")
    rng = np.random.default_rng(0)
    rows = []
    if pallas and any(L <= pallas_max_len for L in lengths):
        # untimed warm-up: the first Pallas dispatch of a process pays
        # one-time tracing/infra costs that would otherwise land on the
        # first timed row (and differ between a cold CI run and the warm
        # process that records the committed baseline)
        wch = _chain(8, np.random.default_rng(123))
        wbudget = simulate(wch, Schedule.store_all(8)).peak_mem * 0.5
        for wimpl in ("pallas", "pallas_fused"):
            solve_optimal(wch, wbudget, num_slots=32, impl=wimpl, cache=False)

    def row(L, impl, dt, sol):
        r = dict(L=L, num_slots=num_slots, impl=impl, solve_s=round(dt, 4),
                 feasible=bool(sol.feasible),
                 expected_time=float(sol.expected_time),
                 table_bytes=int(sol.table_bytes))
        emit(f"{L},{num_slots},{impl},{dt:.3f},{sol.feasible},"
             f"{sol.expected_time:.2f},{sol.table_bytes}")
        rows.append(r)
        return r

    for L in lengths:
        ch = _chain(L, rng)
        peak = simulate(ch, Schedule.store_all(L)).peak_mem
        budget = peak * 0.4
        dt_b, sol_b = _best_of(
            lambda: solve_optimal(ch, budget, num_slots=num_slots,
                                  cache=False), repeats)
        row(L, "banded", dt_b, sol_b)
        if prune_rows:
            with _pruning_disabled():
                dt_np, sol_np = _best_of(
                    lambda: solve_optimal(ch, budget, num_slots=num_slots,
                                          cache=False), repeats)
            r = row(L, "banded-noprune", dt_np, sol_np)
            r["pruning_speedup"] = round(dt_np / max(dt_b, 1e-9), 2)
            assert sol_np.feasible == sol_b.feasible
            if sol_b.feasible:
                assert sol_np.expected_time == sol_b.expected_time
        if pallas:
            if L <= pallas_max_len:
                dt_p, sol_p = _best_of(
                    lambda: solve_optimal(ch, budget, num_slots=num_slots,
                                          impl="pallas", cache=False), 1)
                r = row(L, "pallas", dt_p, sol_p)
                r["ratio_vs_banded"] = round(dt_p / max(dt_b, 1e-9), 2)
                assert sol_p.feasible == sol_b.feasible
                if sol_b.feasible:
                    assert sol_p.expected_time == sol_b.expected_time
                # untimed pre-solve: resolves (and memoizes) the autotuner's
                # block_rows choice so that — under REPRO_DP_AUTOTUNE=1 —
                # calibration fills neither land in the timed window nor in
                # the dispatch count below
                solve_optimal(ch, budget, num_slots=num_slots,
                              impl="pallas_fused", cache=False)
                with _count_dispatches() as calls:
                    dt_f, sol_f = _best_of(
                        lambda: solve_optimal(ch, budget, num_slots=num_slots,
                                              impl="pallas_fused",
                                              cache=False), repeats)
                r = row(L, "pallas_fused", dt_f, sol_f)
                r["ratio_vs_banded"] = round(dt_f / max(dt_b, 1e-9), 2)
                r["device_dispatches"] = calls[0] // repeats
                assert calls[0] == repeats, (
                    f"fused fill made {calls[0]} dispatches over {repeats} "
                    f"fills (expected 1 per fill)")
                assert sol_f.feasible == sol_b.feasible
                if sol_b.feasible:
                    assert sol_f.expected_time == sol_b.expected_time
            else:
                emit(f"# pallas/pallas_fused: skipped at L={L} "
                     f"(interpret mode; rows capped at "
                     f"L<={pallas_max_len})")
        if reference:
            dt_r, sol_r = _best_of(
                lambda: solve_optimal(ch, budget, num_slots=num_slots,
                                      impl="reference", cache=False), 1)
            r = row(L, "reference", dt_r, sol_r)
            r["speedup_vs_reference"] = round(dt_r / max(dt_b, 1e-9), 2)
            r["table_shrink"] = round(sol_r.table_bytes
                                      / max(sol_b.table_bytes, 1), 2)
            assert sol_b.feasible == sol_r.feasible
            if sol_b.feasible:
                assert abs(sol_b.expected_time - sol_r.expected_time) \
                    <= 1e-6 * sol_r.expected_time
        if offload:
            # host link priced so transfers are comparable to compute —
            # offload-vs-keep decisions stay non-trivial at this scale
            hch = ch.with_host(HostTransferModel(bandwidth_d2h=2.0))
            dt_o, sol_o = _best_of(
                lambda: solve_optimal_offload(hch, budget,
                                              num_slots=num_slots,
                                              cache=False), 1)
            r = row(L, "offload", dt_o, sol_o)
            r["ratio_vs_banded_two_tier"] = round(dt_o / max(dt_b, 1e-9), 2)

    # cached relaunch: the second identical solve skips the DP entirely
    ch = _chain(lengths[-1], np.random.default_rng(1))
    budget = simulate(ch, Schedule.store_all(ch.length)).peak_mem * 0.4
    solve_optimal(ch, budget, num_slots=num_slots)
    t0 = time.perf_counter()
    solve_optimal(ch, budget, num_slots=num_slots)
    cached_s = time.perf_counter() - t0
    emit(f"# cached re-solve at L={ch.length}: {cached_s * 1e3:.2f} ms")

    result = dict(bench="solver", num_slots=num_slots, rows=rows,
                  cached_resolve_s=round(cached_s, 6))
    big = [r for r in rows if r["impl"] == "reference"
           and "speedup_vs_reference" in r]
    if big:
        last = big[-1]
        result["headline"] = dict(
            L=last["L"], num_slots=num_slots,
            reference_s=last["solve_s"],
            banded_s=next(r["solve_s"] for r in rows
                          if r["impl"] == "banded" and r["L"] == last["L"]),
            speedup=last["speedup_vs_reference"],
            table_shrink=last["table_shrink"])
        emit(f"# headline: L={last['L']} speedup={last['speedup_vs_reference']}x "
             f"table_shrink={last['table_shrink']}x")
    return result


def write_json(result: dict, path: str = JSON_PATH) -> None:
    with open(path, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)


def main(emit=print, small: bool = True):
    from .bench_fleet import main as fleet_main
    from .bench_prediction import drift_section
    from .bench_serve import serve_section

    if small:
        result = run(lengths=(20, 50, 100), num_slots=200, emit=emit)
        emit("# prediction drift section (repro.obs trace -> calibrate):")
        result["prediction"] = drift_section(emit=emit, small=True)
        emit("# fleet section (cold-vs-warm plan store, frontier query):")
        result["fleet"] = fleet_main(emit=emit, small=True)
        emit("# serve section (planned vs naive KV residency):")
        result["serve"] = serve_section(emit=emit, small=True)
        return result
    result = run(emit=emit)
    # Embed the CI-sized run too: the bench-trajectory job replays exactly
    # `--small` on the runner and diffs its rows against this section of the
    # committed baseline (same lengths, same slot count — comparable rows).
    emit("# small (CI bench-trajectory baseline) rows:")
    result["small"] = run(lengths=(20, 50, 100), num_slots=200, emit=emit)
    emit("# prediction drift section (repro.obs trace -> calibrate):")
    result["prediction"] = drift_section(emit=emit, small=True)
    emit("# fleet section (cold-vs-warm plan store, frontier query):")
    result["fleet"] = fleet_main(emit=emit, small=False)
    emit("# serve section (planned vs naive KV residency):")
    result["serve"] = serve_section(emit=emit, small=True)
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="CI smoke sizes (L<=100, S=200)")
    ap.add_argument("--json", default=JSON_PATH,
                    help="where to write the machine-readable results")
    args = ap.parse_args()
    res = main(small=args.small)
    write_json(res, args.json)
    print(f"wrote {args.json}")
