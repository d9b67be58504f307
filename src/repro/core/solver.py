"""Optimal persistent checkpointing DP — paper Theorem 1 / Algorithms 1 & 2.

``C[s, t, m]`` = optimal makespan to backprop the sub-chain ``[s, t]`` (paper
numbering, ``1 <= s <= t <= L+1``) with ``m`` memory slots, given that the
input ``a^{s-1}`` and the gradient ``δ^t`` are live, with ``a^{s-1}`` *not*
counted against ``m`` (``δ^t`` *is* counted — it appears in the
:math:`m_\\varnothing`/:math:`m_{all}` thresholds).

Four fill implementations share the recursion (``dp_kernels.KNOWN_IMPLS``):

- ``impl="banded"`` (default): the length-banded, split-batched float32
  kernels of :mod:`repro.core.dp_kernels` — all starts of a sub-chain length
  are processed together, one vectorized candidate plane per split, over
  pre-shifted companion tables; the cost tables are upper-triangular bands
  (~5.5× smaller than the seed layout), and branch choices are recomputed at
  the O(L) cells the reconstruction visits instead of being stored.
  ``expected_time`` is recomputed in float64 by the simulator, so the
  published makespan is exact.
- ``impl="pallas"``: the same band recursion with the split-batched min
  reduction on the per-band Pallas kernel of :mod:`repro.kernels.dp_fill` —
  compiled for a TPU (interpret mode only when asked for); band-exact against
  ``"banded"`` (tested on f32-exact chains).  The band loop stays on the
  host: O(L) kernel dispatches per fill.
- ``impl="pallas_fused"``: the whole band recursion in ONE ``pallas_call``
  (same package) — companion tables are rebuilt in-kernel, output bands
  accumulate in device-resident buffers sized by the saturation-cap band
  width, and the host touches the tables exactly twice (upload base case,
  download result).  Also band-exact against ``"banded"``.
- ``impl="reference"``: the original per-cell float64 fill, retained as the
  slow-but-transparent comparator (kernel-equivalence tests and benchmarks
  diff the implementations).

All three share the saturated m-column pruning pass
(:func:`repro.core.dp_kernels.saturation_caps`): per-band column frontiers
are computed before any fill runs, each band is filled only up to its
frontier, and the saturated tail is broadcast — bit-identical tables for a
fraction of the work (``REPRO_DP_PRUNE=0`` disables).

Results are memoized through :mod:`repro.core.solver_cache` (in-memory LRU +
on-disk store keyed by a content hash of the discretized problem), so
repeated launches and budget sweeps skip the DP fill entirely.

Outputs:
- the optimal op ``Schedule`` (Algorithm 2),
- the equivalent recursion *tree* consumed by ``rematerialize.py`` to build a
  nested ``jax.checkpoint`` function,
- the predicted makespan, for validation against the simulator (they must
  agree exactly — tested).
"""

from __future__ import annotations

import dataclasses
import os
from typing import List, Optional, Tuple, Union

import numpy as np

from . import dp_kernels, solver_cache
from ..obs import metrics as _obs
from .chain import Chain
from .dp_kernels import (INFEASIBLE, _m_all, _m_none, _shift,  # noqa: F401
                         _views)
from .schedule import BWD, F_ALL, F_CK, F_NONE, Schedule, simulate


def _resolve_impl(impl: Optional[str]) -> str:
    impl = impl or os.environ.get("REPRO_DP_IMPL", "banded")
    if impl not in dp_kernels.KNOWN_IMPLS:
        raise ValueError(f"unknown DP impl {impl!r}; "
                         f"expected one of {dp_kernels.KNOWN_IMPLS}")
    return impl


# ---------------------------------------------------------------------------
# Recursion tree (consumed by the nested-remat compiler)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Leaf:
    """Stage ``s`` executed as ``F_all^s`` immediately followed by ``B^s``."""
    s: int


@dataclasses.dataclass
class AllNode:
    """``F_all^s`` first: stage ``s`` residuals are recorded, rest recurses."""
    s: int
    rest: "Tree"


@dataclasses.dataclass
class CkNode:
    """``F_ck^s`` first: segment ``[s, sp-1]`` streamed with ``F_∅`` (its input
    ``a^{s-1}`` checkpointed), then ``[sp, t]`` solved, then ``[s, sp-1]``
    re-solved recursively."""
    s: int
    sp: int
    right: "Tree"   # sub-chain [sp, t]
    left: "Tree"    # sub-chain [s, sp-1], executed after `right`'s backward


Tree = Union[Leaf, AllNode, CkNode]


@dataclasses.dataclass
class Solution:
    feasible: bool
    expected_time: float
    schedule: Optional[Schedule]
    tree: Optional[Tree]
    mem_limit: float
    num_slots: int
    slots_used: int
    # DP diagnostics
    table_bytes: int = 0


# ---------------------------------------------------------------------------
# Reference DP tables (the seed implementation, kept as the slow comparator)
# ---------------------------------------------------------------------------

class _Tables:
    """Raw DP tables; index convention: C[s, t, m] with 1-based s,t."""

    def __init__(self, L: int, S: int):
        self.L, self.S = L, S
        shape = (L + 2, L + 2, S + 1)
        self.C = np.full(shape, INFEASIBLE, dtype=np.float64)
        # choice: 0 = infeasible, 1 = Ck (split stored in `split`), 2 = All
        self.choice = np.zeros(shape, dtype=np.int8)
        self.split = np.zeros(shape, dtype=np.int16)

    @property
    def nbytes(self) -> int:
        return self.C.nbytes + self.choice.nbytes + self.split.nbytes


def _fill_tables(dchain, tables: _Tables, allow_fall: bool = True,
                 prune: Optional[bool] = None) -> None:
    """Bottom-up DP fill.  ``allow_fall=False`` disables the C2 (``F_all``)
    branch for sub-chains of length > 1 — the revolve comparator.  Saturated
    m-columns are pruned per band (the shared
    :func:`repro.core.dp_kernels.saturation_caps` pass): only columns up to
    the band's frontier are computed and the frontier column is broadcast
    across the rest — bit-identical values, ``REPRO_DP_PRUNE=0`` disables."""
    v = _views(dchain)
    L, S = tables.L, tables.S
    C, choice, split = tables.C, tables.choice, tables.split
    ms = np.arange(S + 1)
    caps = (dp_kernels.saturation_caps(v, S, allow_fall)
            if dp_kernels._resolve_prune(prune) else None)

    # base cases: C[s, s, m]
    for s in range(1, L + 2):
        feas = ms >= _m_all(v, s, s)
        C[s, s, feas] = v["UF"][s] + v["UB"][s]
        choice[s, s, feas] = 2

    # bottom-up by sub-chain length
    for d in range(1, L + 1):
        W = dp_kernels.band_width(caps, d, S)
        msW = ms[:W]
        for s in range(1, L + 2 - d):
            t = s + d

            def bcast():
                if W <= S:
                    C[s, t, W:] = C[s, t, W - 1]
                    choice[s, t, W:] = choice[s, t, W - 1]
                    split[s, t, W:] = split[s, t, W - 1]

            # --- C1: start with F_ck^s, split at s' ----------------------
            sps = np.arange(s + 1, t + 1)
            # candidate[k, m] for split sps[k]
            cand = np.empty((len(sps), W), dtype=np.float64)
            for k, sp in enumerate(sps):
                fwd = v["CUM_UF"][sp - 1] - v["CUM_UF"][s - 1]
                cand[k] = (fwd
                           + _shift(C[sp, t, :W], int(v["WA"][sp - 1]))
                           + C[s, sp - 1, :W])
            best_k = np.argmin(cand, axis=0)
            c1 = cand[best_k, msW]
            c1[msW < _m_none(v, s, t)] = INFEASIBLE
            if not allow_fall:
                C[s, t, :W] = c1
                ch = np.zeros(W, dtype=np.int8)
                ch[np.isfinite(c1)] = 1
                choice[s, t, :W] = ch
                split[s, t, :W] = np.where(ch == 1, sps[best_k],
                                           0).astype(np.int16)
                bcast()
                continue
            # --- C2: start with F_all^s ---------------------------------
            c2 = (v["UF"][s] + _shift(C[s + 1, t, :W], int(v["WABAR"][s]))
                  + v["UB"][s])
            c2[msW < _m_all(v, s, t)] = INFEASIBLE
            # --- combine -------------------------------------------------
            use_all = c2 < c1  # ties -> Ck (arbitrary, both optimal)
            C[s, t, :W] = np.where(use_all, c2, c1)
            ch = np.zeros(W, dtype=np.int8)
            ch[np.isfinite(c1)] = 1
            ch[use_all & np.isfinite(c2)] = 2
            ch[~np.isfinite(C[s, t, :W])] = 0
            choice[s, t, :W] = ch
            split[s, t, :W] = np.where(ch == 1, sps[best_k], 0).astype(np.int16)
            bcast()


# ---------------------------------------------------------------------------
# Reconstruction (Algorithm 2) — both as op sequence and as recursion tree
# ---------------------------------------------------------------------------

def _rebuild(v: dict, tables: _Tables, s: int, t: int, m: int
             ) -> Tuple[List, Tree]:
    """Reference-path reconstruction (``v`` is computed once by the caller
    and threaded through — the per-node ``_views`` rebuild was O(L) each)."""
    ch = tables.choice[s, t, m]
    if ch == 0:
        raise ValueError(f"infeasible sub-problem ({s},{t},{m})")
    if s == t:
        return [(F_ALL, s), (BWD, s)], Leaf(s)
    if ch == 2:
        ops_rest, tree_rest = _rebuild(
            v, tables, s + 1, t, m - int(v["WABAR"][s]))
        return ([(F_ALL, s)] + ops_rest + [(BWD, s)], AllNode(s, tree_rest))
    sp = int(tables.split[s, t, m])
    ops = [(F_CK, s)] + [(F_NONE, j) for j in range(s + 1, sp)]
    ops_right, tree_right = _rebuild(
        v, tables, sp, t, m - int(v["WA"][sp - 1]))
    ops_left, tree_left = _rebuild(v, tables, s, sp - 1, m)
    return ops + ops_right + ops_left, CkNode(s, sp, tree_right, tree_left)


def _rebuild_banded(v: dict, tab: "dp_kernels.BandedTable", s: int, t: int,
                    m: int, allow_fall: bool) -> Tuple[List, Tree]:
    """Banded-path reconstruction: branch choices are recomputed per visited
    cell (the banded fill stores costs only)."""
    ch, sp = dp_kernels.choose_two_tier(v, tab, s, t, m, allow_fall)
    if ch == 0:
        raise ValueError(f"infeasible sub-problem ({s},{t},{m})")
    if s == t:
        return [(F_ALL, s), (BWD, s)], Leaf(s)
    if ch == 2:
        ops_rest, tree_rest = _rebuild_banded(
            v, tab, s + 1, t, m - int(v["WABAR"][s]), allow_fall)
        return ([(F_ALL, s)] + ops_rest + [(BWD, s)], AllNode(s, tree_rest))
    ops = [(F_CK, s)] + [(F_NONE, j) for j in range(s + 1, sp)]
    ops_right, tree_right = _rebuild_banded(
        v, tab, sp, t, m - int(v["WA"][sp - 1]), allow_fall)
    ops_left, tree_left = _rebuild_banded(v, tab, s, sp - 1, m, allow_fall)
    return ops + ops_right + ops_left, CkNode(s, sp, tree_right, tree_left)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def _finish(chain: Chain, mem_limit: float, num_slots: int,
            m_use: int, table_bytes: int, rebuild_fn) -> Solution:
    """Rebuild at ``m_use`` and publish the float64 simulator makespan."""
    ops, tree = rebuild_fn(m_use)
    sched = Schedule(chain.length, ops)
    expected = float(simulate(chain, sched).time)
    return Solution(True, expected, sched, tree, mem_limit, num_slots, m_use,
                    table_bytes)


def solve_optimal(chain: Chain, mem_limit: float, num_slots: int = 500,
                  allow_fall: bool = True, impl: Optional[str] = None,
                  cache: bool = True) -> Solution:
    """Optimal persistent schedule for ``chain`` under ``mem_limit`` memory.

    ``allow_fall=False`` disables the ``C2`` branch for sub-chains of length
    > 1, which restricts checkpoints to plain activations ``a`` — this is the
    **revolve** comparator of the paper (§5.3, third strategy), i.e. the best
    persistent strategy in the Automatic Differentiation model, converted to a
    valid schedule by running ``F_all`` right before each backward.

    ``impl`` picks the fill kernels (``"banded"`` default, ``"pallas"`` /
    ``"pallas_fused"`` for the per-band / single-dispatch Pallas kernels,
    ``"reference"`` for the seed float64 path; env ``REPRO_DP_IMPL``
    overrides the default).  ``cache=False`` bypasses the solver cache
    (used by benchmarks).
    """
    impl = _resolve_impl(impl)
    dchain = chain.discretize(mem_limit, num_slots)

    def solve() -> Solution:
        L, S = dchain.length, num_slots
        m_top = S - int(dchain.wa[0])  # Alg. 1: budget excludes the input a^0
        v = _views(dchain)
        if impl == "reference":
            tables = _Tables(L, S)
            with _obs.histogram("dp_fill.reference.seconds").time():
                _fill_tables(dchain, tables, allow_fall=allow_fall)
            if m_top < 0 or not np.isfinite(tables.C[1, L + 1, m_top]):
                return Solution(False, INFEASIBLE, None, None, mem_limit,
                                num_slots, max(m_top, 0), tables.nbytes)
            ops, tree = _rebuild(v, tables, 1, L + 1, m_top)
            return Solution(True, float(tables.C[1, L + 1, m_top]),
                            Schedule(L, ops), tree, mem_limit, num_slots,
                            m_top, tables.nbytes)
        tab = dp_kernels.fill_tables(dchain, S, impl=impl,
                                     allow_fall=allow_fall, v=v)
        if m_top < 0 or not np.isfinite(tab.row(1, L + 1)[m_top]):
            return Solution(False, INFEASIBLE, None, None, mem_limit,
                            num_slots, max(m_top, 0), tab.nbytes)
        return _finish(chain, mem_limit, num_slots, m_top, tab.nbytes,
                       lambda m: _rebuild_banded(v, tab, 1, L + 1, m,
                                                 allow_fall))

    return solver_cache.memoize_solve("solve_optimal", impl, chain, dchain,
                                      num_slots, allow_fall, cache, solve)


def solve_min_memory(chain: Chain, num_slots: int = 500,
                     allow_fall: bool = True, impl: Optional[str] = None,
                     cache: bool = True) -> Solution:
    """Smallest-memory feasible persistent schedule: run the DP with the
    store-all peak as the limit, then rebuild at the smallest feasible slot
    count.  Used as the planner's fallback when the requested budget is
    infeasible (reports the actual budget it needed)."""
    impl = _resolve_impl(impl)
    peak = simulate(chain, Schedule.store_all(chain.length)).peak_mem
    dchain = chain.discretize(peak, num_slots)

    def solve() -> Solution:
        L, S = dchain.length, num_slots
        w0 = int(dchain.wa[0])
        v = _views(dchain)
        if impl == "reference":
            tables = _Tables(L, S)
            with _obs.histogram("dp_fill.reference.seconds").time():
                _fill_tables(dchain, tables, allow_fall=allow_fall)
            top = tables.C[1, L + 1]
            table_bytes = tables.nbytes
            rebuild_fn = lambda m: _rebuild(v, tables, 1, L + 1, m)  # noqa: E731
        else:
            tab = dp_kernels.fill_tables(dchain, S, impl=impl,
                                         allow_fall=allow_fall, v=v)
            top = tab.row(1, L + 1)
            table_bytes = tab.nbytes
            rebuild_fn = lambda m: _rebuild_banded(v, tab, 1, L + 1, m,  # noqa: E731
                                                   allow_fall)
        feasible = np.where(np.isfinite(top))[0]
        if len(feasible) == 0:
            return Solution(False, INFEASIBLE, None, None, peak, num_slots,
                            0, table_bytes)
        m_min = int(feasible[0])
        budget = (m_min + w0) * dchain.slot_size  # physical mem incl. a^0
        if impl == "reference":
            ops, tree = rebuild_fn(m_min)
            return Solution(True, float(top[m_min]), Schedule(L, ops), tree,
                            budget, num_slots, m_min, table_bytes)
        return _finish(chain, budget, num_slots, m_min, table_bytes,
                       rebuild_fn)

    return solver_cache.memoize_solve("solve_min_memory", impl, chain,
                                      dchain, num_slots, allow_fall, cache,
                                      solve)


def tree_to_schedule(tree: Tree, length: int) -> Schedule:
    """Flatten a recursion tree back into the canonical op sequence."""
    ops: List = []

    def rec(node: Tree):
        if isinstance(node, Leaf):
            ops.extend([(F_ALL, node.s), (BWD, node.s)])
        elif isinstance(node, AllNode):
            ops.append((F_ALL, node.s))
            rec(node.rest)
            ops.append((BWD, node.s))
        else:
            # right spans [sp, t]; left spans [s, sp-1]
            ops.append((F_CK, node.s))
            ops.extend((F_NONE, j) for j in range(node.s + 1, node.sp))
            rec(node.right)
            rec(node.left)

    rec(tree)
    return Schedule(length, ops)
