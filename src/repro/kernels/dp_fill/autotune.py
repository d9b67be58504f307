"""``block_rows`` autotuner for the fused DP band-fill kernel.

The fused fill (``impl="pallas_fused"``) tiles each band's rows into
``(block_rows, W)`` VMEM blocks.  The best tile height depends on the
machine and on the problem shape (row count vs the saturation-capped band
width), so this module measures a short calibration fill over a small
candidate grid and persists the winner through the solver cache
(:mod:`repro.core.solver_cache`) — the same content-addressed
:mod:`repro.store` tier the DP Solutions use (winner entries carry the
``"autotune"`` envelope kind), with the same corruption semantics: a
truncated, garbled, or wrong-shaped entry is treated as a miss and simply
recalibrated.

Calibration is deliberately tiny (a deterministic synthetic chain, sizes
clamped to ``CALIBRATION_L``/``CALIBRATION_S``) and keyed by power-of-two
buckets of ``(L, S)`` plus the dispatch mode, so one measurement serves a
whole neighborhood of problem sizes.

Knobs:

- ``REPRO_DP_BLOCK_ROWS=<n>`` — pin the tile height, no measurement;
- ``REPRO_DP_AUTOTUNE=1`` — calibrate (once per bucket, then cached);
  unset/0 keeps the static :data:`~repro.kernels.dp_fill.kernel
  .DEFAULT_BLOCK_ROWS`, so CI and cold paths never pay the calibration.
"""

from __future__ import annotations

import os
import time
from typing import Iterable, Tuple

import jax
import numpy as np

from ...core import solver_cache
from . import kernel

#: Tile heights the calibration sweeps.  Small is deliberate: the fused
#: kernel's per-step work is O(block_rows · W), and the row counts of real
#: chains (L ≤ a few hundred) do not reward a finer grid.
CANDIDATE_BLOCK_ROWS: Tuple[int, ...] = (8, 32, 128, 256)

#: Calibration fill size ceilings.  Interpret mode executes the kernel in
#: Python, so its calibration chain must stay tiny; compiled dispatch is
#: fast enough to calibrate near the real problem size, where the large
#: tile-height candidates actually differ.
CALIBRATION_L_INTERPRET = 12
CALIBRATION_S_INTERPRET = 32
CALIBRATION_L_COMPILED = 384
CALIBRATION_S_COMPILED = 512

_VERSION = 2

#: Process-local memo of calibrated choices (keyed by :func:`cache_key`) —
#: bounds calibration to once per process even when the persistent solver
#: cache is disabled (``REPRO_SOLVER_CACHE=0``).
_memo: dict = {}


def _bucket(n: int) -> int:
    """Smallest power of two >= n (problems in one bucket share a choice)."""
    return 1 << max(0, int(n - 1).bit_length())


def cache_key(L: int, S: int, interpret: bool) -> str:
    mode = "interpret" if interpret else f"compiled-{jax.default_backend()}"
    lb, sb = _bucket(max(L, 1)), _bucket(max(S, 1))
    return f"dp-fill-autotune-v{_VERSION}-{mode}-L{lb}-S{sb}"


def _calibration_chain(L: int, S: int, interpret: bool):
    """Deterministic f32-exact chain at the (mode-clamped) calibration
    sizes."""
    from ...core.chain import Chain

    cap_l = CALIBRATION_L_INTERPRET if interpret else CALIBRATION_L_COMPILED
    cap_s = CALIBRATION_S_INTERPRET if interpret else CALIBRATION_S_COMPILED
    Lc = max(1, min(L, cap_l))
    Sc = max(4, min(S, cap_s))
    rng = np.random.default_rng(0)
    n = Lc + 1
    ch = Chain.make(
        uf=rng.integers(1, 5, n).astype(float),
        ub=rng.integers(1, 5, n).astype(float),
        wa=rng.integers(1, 4, n).astype(float),
        wabar=rng.integers(1, 6, n).astype(float),
    )
    return ch.discretize(float(Sc), Sc), Sc


def measure(
    L: int,
    S: int,
    interpret: bool,
    candidates: Iterable[int] = CANDIDATE_BLOCK_ROWS,
    repeats: int = 2,
) -> dict:
    """Time the fused two-tier fill per candidate under the given dispatch
    mode; returns the timing dict (``block_rows`` holds the winner).

    Candidates are deduplicated by their *effective* tile height
    ``min(candidate, calibration L)`` — the fill clamps ``block_rows`` to
    the row count, so without this, every candidate above the calibration
    length would measure the identical configuration and the "winner" among
    them would be timer noise.
    """
    from . import ops

    dchain, Sc = _calibration_chain(L, S, interpret)
    Lc = dchain.length
    effective = sorted({min(int(c), Lc) for c in candidates})
    timings = {}
    with ops.interpreting(interpret):
        for br in effective:
            best = None
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                ops.fill_two_tier_fused(dchain, Sc, block_rows=br)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            timings[int(br)] = best
    winner = min(timings, key=timings.get)
    return {"version": _VERSION, "block_rows": int(winner), "timings": timings}


def _valid_entry(entry) -> bool:
    """Guards against a *decodable but wrong-shaped* cache value (the store
    tier already quarantines undecodable bytes as a miss)."""
    return (
        isinstance(entry, dict)
        and entry.get("version") == _VERSION
        and isinstance(entry.get("block_rows"), int)
        and entry["block_rows"] >= 1
    )


def autotune_block_rows(
    L: int,
    S: int,
    *,
    interpret: bool,
    candidates: Iterable[int] = CANDIDATE_BLOCK_ROWS,
    cache: bool = True,
) -> int:
    """The calibrated tile height for an ``(L, S)``-sized fill; measured at
    most once per ``(bucket, dispatch-mode)`` and persisted via the solver
    cache's disk store.  A corrupted or stale persisted entry recalibrates
    (and is overwritten), mirroring :mod:`repro.core.solver_cache`."""
    from ...obs import metrics as _obs

    sc = solver_cache.get_cache()
    key = cache_key(L, S, interpret)
    if cache:
        if key in _memo:
            return _memo[key]
        if sc.enabled:
            entry = sc.get(key)
            if _valid_entry(entry):
                _memo[key] = entry["block_rows"]
                _obs.counter("dp_autotune.cache_hits").inc()
                _obs.gauge("dp_autotune.block_rows").set(entry["block_rows"])
                return entry["block_rows"]
    result = measure(L, S, interpret, candidates=candidates)
    if cache:
        _memo[key] = result["block_rows"]
        if sc.enabled:
            sc.put(key, result, kind="autotune")
    _obs.counter("dp_autotune.calibrations").inc()
    _obs.gauge("dp_autotune.block_rows").set(result["block_rows"])
    return result["block_rows"]


def resolve_block_rows(L: int, S: int, *, interpret: bool) -> int:
    """The fused fill's tile height: pinned by ``REPRO_DP_BLOCK_ROWS``,
    calibrated when ``REPRO_DP_AUTOTUNE`` is truthy, else the static
    default (no measurement on cold paths)."""
    pinned = os.environ.get("REPRO_DP_BLOCK_ROWS")
    if pinned:
        try:
            return max(1, int(pinned))
        except ValueError:
            raise ValueError(
                f"cannot parse REPRO_DP_BLOCK_ROWS={pinned!r}: expected a "
                f"positive integer tile height, e.g. 128"
            ) from None
    flag = os.environ.get("REPRO_DP_AUTOTUNE", "0").lower()
    if flag not in ("0", "false", "off", ""):
        return autotune_block_rows(L, S, interpret=interpret)
    return kernel.DEFAULT_BLOCK_ROWS
