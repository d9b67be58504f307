"""Banded, split-batched DP kernels for the checkpointing solvers.

The seed implementation (``core/solver.py::_fill_tables`` and
``offload/solver.py::_fill_tables_offload``) walks every sub-chain ``(s, t)``
in a Python double loop and, per cell, builds a ``(num_splits, S+1)``
candidate matrix with one ``_shift`` allocation per split — ~``L^3/6`` tiny
numpy calls at paper scale (L=339 / S=500), which is why plan-time dominated
every launch.  This module restructures the same recursion around *length
bands*:

- tables are stored upper-triangular only (``1 <= s <= t <= L+1``), one
  contiguous block per sub-chain length ``d = t - s``, in **float32** — no
  ``choice``/``split`` tables at all (branch decisions are recomputed at the
  O(L) cells the reconstruction actually visits, see :func:`choose_two_tier`);
- for each length ``d`` the candidate planes of **all** starts ``s`` are
  evaluated split-by-split into a running minimum.  Two companion tables,
  built once per cell with contiguous copies, collapse the C1 candidate to a
  *single add per split*:  ``R[s',t][m] = C[s',t][m - WA[s'-1]] + CUM[s'-1]``
  (the per-split memory shift pre-applied, with a ``+inf`` sentinel column
  absorbing out-of-budget reads) and ``Lm[s,t][m] = C[s,t][m] - CUM[s-1]`` —
  the forward-stream cost ``CUM[sp-1] - CUM[s-1]`` telescopes away;
- the offload C3 plane folds its stall into a max
  (``X + max(T_off - X, 0) = max(X, T_off)``) and reads the same ``R`` at a
  parent-side column offset, so it too needs no gather;
- all per-band scratch planes are preallocated once and re-sliced across
  lengths, and big bands fan the split loop out over a small thread pool
  (exact: min-accumulation does not round).

Memory: the seed kept ``(L+2)^2 (S+1)`` cells ×11 B (two-tier: float64 cost +
int8 choice + int16 split; ×2 tables for offload) — ~640 MB / ~1.3 GB at
paper scale.  The band layout keeps ``(L+1)(L+2)/2`` cells × 4 B — a ~5.5×
shrink (``Solution.table_bytes`` reports it).

Exactness: costs are float32, but every quantity the tier-1 test chains
produce (integer stage costs, dyadic transfer times) is exactly representable
in float32 below 2^24, so the banded DP is bit-equal to the float64 reference
there; ``solve_optimal`` recomputes ``expected_time`` of the reconstructed
schedule in float64 via the simulator, so the published makespan is exact
regardless of the table dtype.

The fills all share a *saturated m-column pruning* pass
(:func:`saturation_caps`): ``C[s, t, m]`` is constant in ``m`` beyond a
per-band frontier (once every threshold is passed and every child read lands
in the child's own constant region, more memory cannot change any candidate),
and the frontier is computable from the thresholds and shift widths alone —
before any fill runs.  Each band is therefore filled only up to its frontier
column and the last computed column is broadcast across the rest; the result
is bit-identical to the unpruned fill (tested), but small-length bands — the
ones with the most rows — shrink to a few dozen columns.  ``REPRO_DP_PRUNE=0``
disables pruning globally (every fill also takes an explicit ``prune=``).

Four implementations share this recursion end to end (``KNOWN_IMPLS``):
``"banded"`` (this module's numpy kernels), ``"reference"`` (the seed
per-cell float64 fill in the solvers), ``"pallas"`` (the per-band Pallas
kernel of :mod:`repro.kernels.dp_fill` — host-driven band loop, one launch
per length), and ``"pallas_fused"`` (the same package's device-resident fill:
ONE ``pallas_call`` runs the whole recursion with in-kernel companion
rebuild, buffers sized by the :func:`saturation_caps` band-width bound).
The Pallas impls are dispatched lazily by :func:`fill_tables` /
:func:`fill_tables_offload` so the numpy core never imports jax.
"""

from __future__ import annotations

import concurrent.futures
import os
from typing import Optional, Tuple

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from ..obs import metrics as _obs

INFEASIBLE = np.inf
COST_DTYPE = np.float32
_F32 = np.float32
_INF32 = np.float32(np.inf)

#: The DP fill implementations every solver entry point accepts.
KNOWN_IMPLS = ("banded", "reference", "pallas", "pallas_fused")


def _resolve_prune(prune: Optional[bool]) -> bool:
    """Saturated m-column pruning default: on, unless ``REPRO_DP_PRUNE``
    says otherwise (``0``/``false``/``off``)."""
    if prune is not None:
        return bool(prune)
    return os.environ.get("REPRO_DP_PRUNE", "1").lower() not in (
        "0", "false", "off")

# The split loop parallelizes exactly (each split's candidate plane is
# independent; min-accumulation is order-free — IEEE min does not round), so
# big bands are fanned out over a small thread pool: numpy ufuncs release the
# GIL on these contiguous float32 planes.  ``REPRO_DP_THREADS=1`` forces the
# serial path.
_pool: Optional[concurrent.futures.ThreadPoolExecutor] = None
_pool_size = 0
# thread only bands whose total candidate volume amortizes the dispatch
_PAR_MIN_ELEMS = 1 << 21


def _n_workers(default_parallel: bool = True) -> int:
    env = os.environ.get("REPRO_DP_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    if not default_parallel:
        return 1
    return max(1, min(4, os.cpu_count() or 1))


def _executor(n: int) -> concurrent.futures.ThreadPoolExecutor:
    global _pool, _pool_size
    if _pool is None or _pool_size < n:
        _pool = concurrent.futures.ThreadPoolExecutor(max_workers=n)
        _pool_size = n
    return _pool


# ---------------------------------------------------------------------------
# 1-based views of a DiscreteChain (shared by fills, chooses, and rebuilds)
# ---------------------------------------------------------------------------

def _views(dchain) -> dict:
    """1-based views aligned with paper notation (see chain.py docstring)."""
    L = dchain.length
    uf = np.concatenate([[0.0], dchain.uf])          # UF[l], l=1..L+1
    ub = np.concatenate([[0.0], dchain.ub])
    wabar = np.concatenate([[0], dchain.wabar])      # WABAR[l]
    of = np.concatenate([[0], dchain.of])
    ob = np.concatenate([[0], dchain.ob])
    wa = np.asarray(dchain.wa)                       # WA[i], i=0..L
    wd = np.concatenate([dchain.wdelta, [0]])        # WD[i], i=0..L+1 (δ^{L+1}=0)
    cum_uf = np.cumsum(uf)                           # cum_uf[l] = Σ_{k<=l} UF[k]
    return dict(L=L, UF=uf, UB=ub, WA=wa, WABAR=wabar, OF=of, OB=ob, WD=wd,
                CUM_UF=cum_uf)


def _shift(vec: np.ndarray, w: int) -> np.ndarray:
    """shifted[m] = vec[m - w]: positive ``w`` is a memory *reduction*
    (entries below ``w`` become inf), negative ``w`` a memory *gain* (used by
    the offload DP when a checkpoint's device slots are reclaimed; lookups
    beyond the table clamp to the last column — ``vec`` is non-increasing in
    ``m`` and budgets above the total slot count are physically meaningless).
    """
    if w == 0:
        return vec
    out = np.full_like(vec, INFEASIBLE)
    if w > 0:
        if w < len(vec):
            out[w:] = vec[: len(vec) - w]
        return out
    k = -w
    if k < len(vec):
        out[: len(vec) - k] = vec[k:]
        out[len(vec) - k:] = vec[-1]
    else:
        out[:] = vec[-1]
    return out


def _m_all(v: dict, s: int, t: int) -> int:
    return int(max(v["WD"][t] + v["WABAR"][s] + v["OF"][s],
                   v["WD"][s] + v["WABAR"][s] + v["OB"][s]))


def _m_none(v: dict, s: int, t: int) -> int:
    best = v["WD"][t] + v["WA"][s] + v["OF"][s]
    js = np.arange(s + 1, t)
    if len(js):
        best = max(best, (v["WD"][t] + v["WA"][js - 1] + v["WA"][js]
                          + v["OF"][js]).max())
    return int(best)


def _h_vector(v: dict) -> np.ndarray:
    """H[j] = WA[j-1] + WA[j] + OF[j] (the F_∅-stream liveness of a^{j-1},
    a^j plus the forward overhead), j = 1..L — windows of it give m_∅."""
    L = v["L"]
    WA = np.asarray(v["WA"], dtype=np.int64)
    H = np.zeros(L + 1, dtype=np.int64)
    if L >= 1:
        H[1:] = WA[:-1] + WA[1:] + np.asarray(v["OF"][1:L + 1], dtype=np.int64)
    return H


def _band_thresholds(v: dict, H: np.ndarray, d: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """(m_all, m_none) for every start ``s = 1..L+1-d`` at length ``d``."""
    L = v["L"]
    ns = L + 1 - d
    sv = np.arange(1, ns + 1)
    tv = sv + d
    WD, OF, OB = v["WD"], v["OF"], v["OB"]
    WA = np.asarray(v["WA"], dtype=np.int64)
    WB = np.asarray(v["WABAR"], dtype=np.int64)
    ma = np.maximum(WD[tv] + WB[sv] + OF[sv].astype(np.int64),
                    WD[sv] + WB[sv] + OB[sv].astype(np.int64))
    base = WA[sv] + OF[sv].astype(np.int64)
    if d >= 2:
        wmax = sliding_window_view(H[2:L + 1], d - 1)[:ns].max(axis=1)
        mn = WD[tv] + np.maximum(base, wmax)
    else:
        mn = WD[tv] + base
    return ma, mn


def saturation_caps(v: dict, S: int, allow_fall: bool = True) -> np.ndarray:
    """Per-band saturated-column frontier, computable *before any fill runs*.

    ``caps[d]`` is a column index ``c <= S`` such that every cell of band
    ``d`` is constant in ``m`` on ``[c, S]``.  Induction: a base-case cell is
    ``+inf`` below its ``m_all`` threshold and constant above it; a band-``d``
    cell at ``m >= caps[d]`` has every threshold passed (``caps[d]`` majorizes
    the band's ``m_∅``/``m_all``) and every candidate read lands at column
    ``m - w >= caps[d-1]`` (``caps[d] >= caps[d-1] + wshift`` with ``wshift``
    the largest in-table memory shift) — i.e. in the child's own constant
    region — so no candidate, and hence no min, can change with ``m``.  The
    offload C3 memory-*gain* reads land at columns ``> m``, which the same
    argument covers.  Shifts beyond ``S+1`` read the ``+inf`` sentinel at
    every ``m`` and are constant trivially, so ``wshift`` clips there.

    The fills use the caps to compute each band only on ``[0, caps[d]]`` and
    broadcast column ``caps[d]`` across the rest — bit-identical to the
    unpruned fill, but the small-length bands (the ones with the most rows)
    shrink to a few dozen columns.
    """
    L = v["L"]
    H = _h_vector(v)
    WA = np.asarray(v["WA"], dtype=np.int64)
    WB = np.asarray(v["WABAR"], dtype=np.int64)
    wshift = int(np.minimum(WA, S + 1).max(initial=0))
    if allow_fall:
        wshift = max(wshift, int(np.minimum(WB[1:], S + 1).max(initial=0)))
    caps = np.empty(L + 1, dtype=np.int64)
    sv = np.arange(1, L + 2)
    ma0 = (v["WD"][sv] + WB[sv]
           + np.maximum(v["OF"][sv], v["OB"][sv]).astype(np.int64))
    caps[0] = min(S, max(0, int(ma0.max())))
    for d in range(1, L + 1):
        ma, mn = _band_thresholds(v, H, d)
        t = int(mn.max())
        if allow_fall:
            t = max(t, int(ma.max()))
        caps[d] = min(S, max(t, int(caps[d - 1]) + wshift))
    return caps


def band_width(caps: Optional[np.ndarray], d: int, S: int) -> int:
    """Number of columns band ``d`` must actually compute (``S+1`` unpruned)."""
    if caps is None:
        return S + 1
    return min(S + 1, int(caps[d]) + 1)


# ---------------------------------------------------------------------------
# Band storage
# ---------------------------------------------------------------------------

class BandedTable:
    """Upper-triangular cost table ``C[s, t, m]`` (``1 <= s <= t <= L+1``,
    ``0 <= m <= S``), stored as one contiguous float32 block per sub-chain
    length ``d = t - s``.

    Storage column 0 is a hidden ``+inf`` sentinel: gather indices are the
    memory index **plus one**, clipped to ``[0, S+1]``, so an out-of-budget
    shift reads infeasibility directly and the fill needs no masking pass.
    ``row(s, t)`` returns the m-indexed view (sentinel excluded).
    """

    def __init__(self, L: int, S: int):
        self.L, self.S = L, S
        sizes = np.array([L + 1 - d for d in range(L + 1)], dtype=np.int64)
        self.off = np.concatenate([[0], np.cumsum(sizes)])  # off[d] band start
        self.data = np.full((int(self.off[-1]), S + 2), INFEASIBLE,
                            dtype=COST_DTYPE)

    def band(self, d: int) -> np.ndarray:
        """Rows for all sub-chains of length ``d`` (s = 1..L+1-d), incl. the
        sentinel column."""
        return self.data[self.off[d]:self.off[d + 1]]

    def row(self, s: int, t: int) -> np.ndarray:
        """``C[s, t, :]`` — the (S+1,) cost vector over memory slots."""
        return self.data[self.off[t - s] + (s - 1), 1:]

    @property
    def nbytes(self) -> int:
        return self.data.nbytes


class _Scratch:
    """Preallocated per-fill scratch: a handful of ``(L+1, S+1)``-sized
    planes re-sliced across band lengths and split offsets.  The fills
    accumulate a running minimum over splits instead of materializing the
    full ``(num_s, num_splits, S+1)`` candidate tensor, so the working set
    per numpy op stays cache-resident."""

    def __init__(self, L: int, S: int, planes: int, iplanes: int = 2):
        ncols = S + 1
        self.f32 = [np.empty((L + 1) * ncols, dtype=COST_DTYPE)
                    for _ in range(planes)]
        self.i32 = [np.empty((L + 1) * ncols, dtype=np.int32)
                    for _ in range(iplanes)]

    def plane(self, k: int, ns: int, ncols: int) -> np.ndarray:
        return self.f32[k][:ns * ncols].reshape(ns, ncols)

    def iplane(self, k: int, ns: int, ncols: int) -> np.ndarray:
        return self.i32[k][:ns * ncols].reshape(ns, ncols)


class _FillCtx:
    """Everything a band fill needs that is independent of the band length."""

    def __init__(self, v: dict, L: int, S: int):
        self.v, self.L, self.S = v, L, S
        self.S1, self.S2 = S + 1, S + 2
        ms = np.arange(S + 1)
        self.ms = ms
        WA = np.asarray(v["WA"], dtype=np.int64)        # (L+1,) a^0..a^L
        WB = np.asarray(v["WABAR"], dtype=np.int64)     # (L+2,) 1-based
        self.WA, self.WB = WA, WB
        # storage-column gather indices (sentinel layout: column = m - w + 1,
        # clipped to [0, S+1]; 0 reads +inf, S+1 reads m = S)
        self.idx_wb = np.clip(ms[None, :] - WB[:, None] + 1,
                              0, S + 1).astype(np.int32)
        # raw (unclipped) m - WA[p], for the offload branch whose shift also
        # depends on the group input; clamped low so int32 cannot overflow
        # after adding WA[s-1] back (values below -2^30 are equally infeasible)
        self.raw_wa = np.clip(ms[None, :] - WA[:, None],
                              -(1 << 30), S).astype(np.int32)
        # flat-storage row strides: is2[i] = i * (S+2)
        self.is2 = (np.arange(L + 1, dtype=np.int64) * self.S2
                    ).astype(np.int32)
        # Activation sizes come quantized into few distinct slot counts, so
        # per-row shifted reads are done as one contiguous block copy per
        # distinct WA value.  groups[w] lists the p's (= band row indices of
        # the cells whose *input* is a^p) with min(WA[p], S+1) == w.
        wvals = np.minimum(WA, S + 1)
        self.groups = [(int(w), np.nonzero(wvals == w)[0])
                       for w in np.unique(wvals)]
        self.wcap = int(wvals.max(initial=0))
        # True when no activation exceeds the whole budget — the precondition
        # for the slice-based (gather-free) C3 plane
        self.wa_uncapped = bool(WA.max(initial=0) <= S + 1)
        self.UF32 = v["UF"].astype(COST_DTYPE)
        self.UB32 = v["UB"].astype(COST_DTYPE)
        self.CUM = v["CUM_UF"]
        # CUM32[i] = float32 cumulative forward time up to stage i.  The fill
        # bakes it into the companion tables (see fill_two_tier) so the C1
        # candidate is a single add per split: the forward-stream cost
        # fwd = CUM[sp-1] - CUM[s-1] telescopes into
        # (C_right + CUM[sp-1]) + (C_left - CUM[s-1]).
        self.CUM32 = v["CUM_UF"].astype(COST_DTYPE)
        OF, OB, WD = v["OF"], v["OB"], v["WD"]
        self.OF, self.OB, self.WD = OF, OB, WD
        self.H = _h_vector(v)

    def thresholds(self, d: int) -> Tuple[np.ndarray, np.ndarray]:
        """(m_all, m_none) for every start ``s = 1..L+1-d`` at length d."""
        return _band_thresholds(self.v, self.H, d)

    def base_case(self, tab: BandedTable) -> None:
        """``C[s, s, m] = u_f^s + u_b^s`` wherever ``m >= m_all(s, s)``."""
        L = self.L
        sv = np.arange(1, L + 2)
        ma = (self.WD[sv] + self.WB[sv]
              + np.maximum(self.OF[sv], self.OB[sv]).astype(np.int64))
        vals = (self.v["UF"][sv] + self.v["UB"][sv]).astype(COST_DTYPE)
        band0 = tab.band(0)[:, 1:]
        band0[:] = np.where(self.ms[None, :] >= ma[:, None],
                            vals[:, None], _INF32)


def _build_r_band(ctx: _FillCtx, R: np.ndarray, tab: BandedTable, d: int,
                  clamp_tail: bool) -> None:
    """Publish band ``d`` of the pre-shifted right-child companion table:
    ``R[s', t][m'] = C[s', t][m' - WA[s'-1]] + CUM32[s'-1]`` (``+inf`` below
    the shift, and — when ``clamp_tail`` — clamped to ``C[·][S]`` above it,
    the offload DP's memory-gain semantics).  Built once per cell with one
    contiguous copy per distinct WA value; every parent's right-child read
    then becomes a plain block slice instead of a gather."""
    ns = ctx.L + 1 - d
    width = R.shape[1]
    S1 = ctx.S1
    Rband = R[tab.off[d]:tab.off[d] + ns]
    Cband = tab.band(d)
    for w, ps in ctx.groups:
        rows = ps[:np.searchsorted(ps, ns)]
        if len(rows) == 0:
            continue
        cum = ctx.CUM32[rows][:, None]
        ncopy = min(S1, width - w)
        if ncopy > 0:
            Rband[rows, w:w + ncopy] = Cband[rows, 1:1 + ncopy] + cum
        if clamp_tail and width - (w + S1) > 0:
            Rband[rows, w + S1:] = Cband[rows, S1:S1 + 1] + cum


def _build_lm_band(ctx: _FillCtx, Lm: np.ndarray, tab: BandedTable, d: int
                   ) -> None:
    """Publish band ``d`` of the left-child companion table:
    ``Lm[s, t][m] = C[s, t][m] - CUM32[s-1]``."""
    ns = ctx.L + 1 - d
    np.subtract(tab.band(d)[:, 1:], ctx.CUM32[:ns, None],
                out=Lm[tab.off[d]:tab.off[d] + ns])


def _fall_plane(ctx: _FillCtx, tab: BandedTable, d: int, ns: int,
                ma: np.ndarray, out: np.ndarray) -> np.ndarray:
    """C2: ``u_f^s + C[s+1, t][m - wā^s] + u_b^s``, masked by m_all.  The
    plane is computed at whatever column width ``out`` has (the pruned band
    width — gather indices are column-aligned, so slicing is exact)."""
    S2 = ctx.S2
    W = out.shape[1]
    rows = ((tab.off[d - 1] + 1 + np.arange(ns, dtype=np.int64)) * S2
            ).astype(np.int32)
    fi = rows[:, None] + ctx.idx_wb[1:1 + ns, :W]
    np.take(tab.data.reshape(-1), fi, out=out)
    out += ctx.UF32[1:1 + ns, None]
    out += ctx.UB32[1:1 + ns, None]
    out[ctx.ms[None, :W] < ma[:, None]] = _INF32
    return out


# ---------------------------------------------------------------------------
# Two-tier fill
# ---------------------------------------------------------------------------

def fill_two_tier(dchain, S: int, allow_fall: bool = True,
                  v: Optional[dict] = None,
                  prune: Optional[bool] = None) -> BandedTable:
    """Banded bottom-up fill of the paper's Theorem-1 recursion: for each
    sub-chain length the C1 candidates of **all** starts are evaluated one
    split offset at a time — one add of two contiguous companion-table
    blocks (``R`` + ``Lm``) per split — into a running minimum.  With
    ``prune`` (default on, env ``REPRO_DP_PRUNE``), each band computes only
    its unsaturated columns (:func:`saturation_caps`) and broadcasts the
    saturated tail."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tab = BandedTable(L, S)
    ctx.base_case(tab)
    caps = saturation_caps(v, S, allow_fall) if _resolve_prune(prune) else None
    nw = _n_workers()
    scratch = _Scratch(L, S, planes=2 * nw + 1, iplanes=0)
    S1 = ctx.S1
    off = tab.off
    # pre-shifted companions (fill scratch, freed with this frame): the C1
    # candidate for split sp collapses to one add —
    #   (C[sp,t][m - WA[sp-1]] + CUM[sp-1]) + (C[s,sp-1][m] - CUM[s-1])
    # = fwd-stream cost + shifted right child + left child.
    R = np.full((int(off[-1]), S1), INFEASIBLE, dtype=COST_DTYPE)
    Lm = np.empty((int(off[-1]), S1), dtype=COST_DTYPE)
    _build_r_band(ctx, R, tab, 0, clamp_tail=False)
    _build_lm_band(ctx, Lm, tab, 0)
    for d in range(1, L + 1):
        ns = L + 1 - d
        W = band_width(caps, d, S)
        ma, mn = ctx.thresholds(d)
        resfull = tab.band(d)[:, 1:]        # starts at +inf; min-accumulated
        res = resfull[:, :W]

        def run(jlo: int, jhi: int, acc: np.ndarray, tmp: np.ndarray):
            for j in range(jlo, jhi):       # split sp = s + 1 + j
                base = int(off[d - 1 - j]) + 1 + j
                np.add(R[base:base + ns, :W], Lm[off[j]:off[j] + ns, :W],
                       out=tmp)
                np.minimum(acc, tmp, out=acc)

        if nw > 1 and d >= 2 * nw and ns * d * W >= _PAR_MIN_ELEMS:
            bounds = np.linspace(0, d, nw + 1).astype(int)
            futs, accs = [], []
            ex = _executor(nw)
            for k in range(nw):
                if bounds[k] == bounds[k + 1]:
                    continue
                acc = scratch.plane(2 * k, ns, W)
                acc[:] = _INF32
                accs.append(acc)
                futs.append(ex.submit(run, int(bounds[k]), int(bounds[k + 1]),
                                      acc, scratch.plane(2 * k + 1, ns, W)))
            for f in futs:
                f.result()
            for acc in accs:
                np.minimum(res, acc, out=res)
        else:
            run(0, d, res, scratch.plane(0, ns, W))
        res[ctx.ms[None, :W] < mn[:, None]] = _INF32
        if allow_fall:
            c2 = scratch.plane(2 * nw, ns, W)
            _fall_plane(ctx, tab, d, ns, ma, c2)
            np.minimum(res, c2, out=res)
        if W <= S:
            resfull[:, W:] = resfull[:, W - 1:W]   # saturated tail
        _build_r_band(ctx, R, tab, d, clamp_tail=False)
        _build_lm_band(ctx, Lm, tab, d)
    return tab


# ---------------------------------------------------------------------------
# Offload (three-tier) fill — the C3 branch is one more candidate plane
# ---------------------------------------------------------------------------

def fill_offload(dchain, S: int, allow_fall: bool = True,
                 v: Optional[dict] = None, prune: Optional[bool] = None
                 ) -> Tuple[BandedTable, BandedTable]:
    """Banded fill of the offload-aware DP: returns ``(Cb, Ce)`` — input bare
    (all three branches) vs input embedded in an ``ā`` (two-tier branches)."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tb, te = BandedTable(L, S), BandedTable(L, S)
    ctx.base_case(tb)
    ctx.base_case(te)
    caps = saturation_caps(v, S, allow_fall) if _resolve_prune(prune) else None
    host = dchain.chain.host
    host_on = host is not None and host.enabled
    tpre32 = dchain.chain.prefetch_times().astype(COST_DTYPE)
    # the offload fill streams ~4 companion tables per split; extra threads
    # thrash the shared cache on typical 2-core runners, so it defaults to
    # serial (REPRO_DP_THREADS opts in)
    nw = _n_workers(default_parallel=False)
    scratch = _Scratch(L, S, planes=5 * nw + 1, iplanes=nw)
    S1, S2 = ctx.S1, ctx.S2
    flat_b = tb.data.reshape(-1)
    offb, offe = tb.off, te.off
    # pre-shifted right-child companion of C_b (right children are always
    # bare) and left-child companions of both tables.  The C3 plane reads R
    # at a parent-side column offset WA[s-1], so R's width is padded by wcap
    # and the tail clamps to C[·][S] (the memory-gain semantics); that slice
    # trick needs every WA <= S+1, else C3 falls back to an explicit gather.
    slice_c3 = host_on and ctx.wa_uncapped
    ncells = int(offb[-1])
    R = np.full((ncells, S1 + (ctx.wcap if slice_c3 else 0)),
                INFEASIBLE, dtype=COST_DTYPE)
    Lmb = np.empty((ncells, S1), dtype=COST_DTYPE)
    Lme = np.empty((ncells, S1), dtype=COST_DTYPE)
    # C3 left-child companion with the prefetch charge pre-added:
    # Lmb3[s, t][m] = (C_b[s, t][m] - CUM32[s-1]) + T_pre(a^{s-1})
    Lmb3 = np.empty((ncells, S1), dtype=COST_DTYPE) if host_on else None
    _build_r_band(ctx, R, tb, 0, clamp_tail=slice_c3)
    _build_lm_band(ctx, Lmb, tb, 0)
    _build_lm_band(ctx, Lme, te, 0)
    # the C3 stall folds into a max:  X + max(T_off - X, 0) = max(X, T_off);
    # in the CUM-shifted domain the threshold is T_off(a^{s-1}) + CUM[s-1]
    toffP = (dchain.chain.offload_times()
             + np.asarray(v["CUM_UF"][:L + 1])).astype(COST_DTYPE)

    def build_lmb3(d: int) -> None:
        ns_ = L + 1 - d
        lo = int(offb[d])
        np.add(Lmb[lo:lo + ns_], tpre32[:ns_, None], out=Lmb3[lo:lo + ns_])

    if host_on:
        build_lmb3(0)
    for d in range(1, L + 1):
        ns = L + 1 - d
        W = band_width(caps, d, S)
        ma, mn = ctx.thresholds(d)
        resb_full = tb.band(d)[:, 1:]
        rese_full = te.band(d)[:, 1:]
        resb = resb_full[:, :W]
        rese = rese_full[:, :W]
        if host_on:
            toffPcol = toffP[:ns, None]
            wacol = ctx.WA[:ns].astype(np.int32)[:, None]
            par_groups = [(w, ps[:np.searchsorted(ps, ns)])
                          for w, ps in ctx.groups]

        def run(jlo: int, jhi: int, accb, acce, acc3, tmp, tmp3, ifi):
            for j in range(jlo, jhi):       # split sp = s + 1 + j
                base = int(offb[d - 1 - j]) + 1 + j
                lo = int(offb[j])
                # C1 keeps the parent's input-state bit in the left child;
                # the right child is always bare (C_b)
                np.add(R[base:base + ns, :W], Lmb[lo:lo + ns, :W], out=tmp)
                np.minimum(accb, tmp, out=accb)
                np.add(R[base:base + ns, :W], Lme[lo:lo + ns, :W], out=tmp)
                np.minimum(acce, tmp, out=acce)
                if not host_on:
                    continue
                # C3 right segment: the group input's slots are reclaimed,
                # so the shift is WA[sp-1] - WA[s-1] — i.e. the R row read
                # at column offset w0 = WA[s-1], fused with the stall max
                if slice_c3:
                    Rblk = R[base:base + ns]
                    for w0, rows in par_groups:
                        if len(rows):
                            tmp3[rows] = np.maximum(
                                Rblk[rows, w0:w0 + W], toffP[rows][:, None])
                else:
                    np.add(ctx.raw_wa[1 + j:1 + j + ns, :W], wacol, out=ifi)
                    np.clip(ifi, -1, S, out=ifi)
                    ifi += 1
                    ifi += ctx.is2[:ns, None]
                    np.take(flat_b[base * S2:], ifi, out=tmp3)
                    tmp3 += ctx.CUM32[1 + j:1 + j + ns, None]
                    np.maximum(tmp3, toffPcol, out=tmp3)
                tmp3 += Lmb3[lo:lo + ns, :W]            # C3 left is bare
                np.minimum(acc3, tmp3, out=acc3)

        c3acc = None
        if nw > 1 and d >= 2 * nw and ns * d * W >= _PAR_MIN_ELEMS:
            bounds = np.linspace(0, d, nw + 1).astype(int)
            futs, accs = [], []
            ex = _executor(nw)
            for k in range(nw):
                if bounds[k] == bounds[k + 1]:
                    continue
                bufs = [scratch.plane(5 * k + i, ns, W) for i in range(5)]
                bufs[0][:] = _INF32
                bufs[1][:] = _INF32
                bufs[2][:] = _INF32
                accs.append(bufs[:3])
                futs.append(ex.submit(
                    run, int(bounds[k]), int(bounds[k + 1]), bufs[0], bufs[1],
                    bufs[2], bufs[3], bufs[4], scratch.iplane(k, ns, W)))
            for f in futs:
                f.result()
            if host_on:
                c3acc = accs[0][2]
            for i, acc in enumerate(accs):
                np.minimum(resb, acc[0], out=resb)
                np.minimum(rese, acc[1], out=rese)
                if host_on and i > 0:
                    np.minimum(c3acc, acc[2], out=c3acc)
        else:
            if host_on:
                c3acc = scratch.plane(2, ns, W)
                c3acc[:] = _INF32
            run(0, d, resb, rese, c3acc, scratch.plane(0, ns, W),
                scratch.plane(3, ns, W), scratch.iplane(0, ns, W))
        infeas = ctx.ms[None, :W] < mn[:, None]
        resb[infeas] = _INF32
        rese[infeas] = _INF32
        if allow_fall:
            c2 = scratch.plane(5 * nw, ns, W)
            _fall_plane(ctx, te, d, ns, ma, c2)         # C2 child is embedded
            np.minimum(resb, c2, out=resb)
            np.minimum(rese, c2, out=rese)
        if host_on:
            c3acc[infeas] = _INF32
            np.minimum(resb, c3acc, out=resb)
        if W <= S:
            resb_full[:, W:] = resb_full[:, W - 1:W]   # saturated tail
            rese_full[:, W:] = rese_full[:, W - 1:W]
        _build_r_band(ctx, R, tb, d, clamp_tail=slice_c3)
        _build_lm_band(ctx, Lmb, tb, d)
        _build_lm_band(ctx, Lme, te, d)
        if host_on:
            build_lmb3(d)
    return tb, te


# ---------------------------------------------------------------------------
# Impl dispatch — the seam every solver-side kernel goes through
# ---------------------------------------------------------------------------

def fill_tables(dchain, S: int, impl: str = "banded",
                allow_fall: bool = True, v: Optional[dict] = None,
                prune: Optional[bool] = None) -> BandedTable:
    """Two-tier band fill behind the ``impl`` seam: ``"banded"`` runs this
    module's numpy kernels; ``"pallas"`` dispatches (lazily, so the numpy
    core never imports jax) to :mod:`repro.kernels.dp_fill` — the per-band
    Pallas kernel, compiled for a TPU; ``"pallas_fused"``
    runs the same package's device-resident fill (one ``pallas_call`` for
    the whole recursion).  All produce the same :class:`BandedTable` layout,
    so reconstruction is impl-agnostic.  (``"reference"`` keeps its own
    table format and stays in the solvers.)

    Fill wall time lands in the ``dp_fill.<impl>.seconds`` histogram of the
    process metrics registry (:mod:`repro.obs.metrics`)."""
    with _obs.histogram(f"dp_fill.{impl}.seconds").time():
        if impl == "pallas":
            from ..kernels.dp_fill import ops as _dp_fill_ops
            return _dp_fill_ops.fill_two_tier(
                dchain, S, allow_fall=allow_fall, v=v, prune=prune)
        if impl == "pallas_fused":
            from ..kernels.dp_fill import ops as _dp_fill_ops
            return _dp_fill_ops.fill_two_tier_fused(
                dchain, S, allow_fall=allow_fall, v=v, prune=prune)
        if impl != "banded":
            raise ValueError(f"fill_tables cannot run impl {impl!r}")
        return fill_two_tier(dchain, S, allow_fall=allow_fall, v=v,
                             prune=prune)


def fill_tables_offload(dchain, S: int, impl: str = "banded",
                        allow_fall: bool = True, v: Optional[dict] = None,
                        prune: Optional[bool] = None
                        ) -> Tuple[BandedTable, BandedTable]:
    """Offload (three-tier) band fill behind the same ``impl`` seam; wall
    time lands in the ``dp_fill.<impl>.offload_seconds`` histogram."""
    with _obs.histogram(f"dp_fill.{impl}.offload_seconds").time():
        if impl == "pallas":
            from ..kernels.dp_fill import ops as _dp_fill_ops
            return _dp_fill_ops.fill_offload(
                dchain, S, allow_fall=allow_fall, v=v, prune=prune)
        if impl == "pallas_fused":
            from ..kernels.dp_fill import ops as _dp_fill_ops
            return _dp_fill_ops.fill_offload_fused(
                dchain, S, allow_fall=allow_fall, v=v, prune=prune)
        if impl != "banded":
            raise ValueError(f"fill_tables_offload cannot run impl {impl!r}")
        return fill_offload(dchain, S, allow_fall=allow_fall, v=v,
                            prune=prune)


# ---------------------------------------------------------------------------
# Choice recomputation (used by the reconstructions instead of stored tables)
# ---------------------------------------------------------------------------

def _lookup(tab: BandedTable, s: int, t: int, m_shifted: int) -> np.float32:
    if m_shifted < 0:
        return _INF32
    return tab.row(s, t)[min(m_shifted, tab.S)]


def _c1_candidates(v: dict, right_tab: BandedTable, left_tab: BandedTable,
                   s: int, t: int, m: int) -> np.ndarray:
    """C1 candidate values for every split, in the exact float32 operation
    order the banded fill used: the forward-stream cost telescopes as
    ``(C_right[m - w] + CUM32[sp-1]) + (C_left[m] - CUM32[s-1])``."""
    sps = np.arange(s + 1, t + 1)
    n = len(sps)
    right = np.empty(n, dtype=COST_DTYPE)
    left = np.empty(n, dtype=COST_DTYPE)
    for k, sp in enumerate(sps):
        right[k] = _lookup(right_tab, sp, t, m - int(v["WA"][sp - 1]))
        left[k] = left_tab.row(s, sp - 1)[m]
    cum32 = v["CUM_UF"].astype(COST_DTYPE)
    return (right + cum32[sps - 1]) + (left - cum32[s - 1])


def _c2_value(v: dict, child_tab: BandedTable, s: int, t: int, m: int
              ) -> np.float32:
    if m < _m_all(v, s, t):
        return _INF32
    val = _lookup(child_tab, s + 1, t, m - int(v["WABAR"][s]))
    return (val + _F32(v["UF"][s])) + _F32(v["UB"][s])


def choose_two_tier(v: dict, tab: BandedTable, s: int, t: int, m: int,
                    allow_fall: bool = True) -> Tuple[int, int]:
    """Recompute the optimal branch at one cell: returns ``(choice, split)``
    with choice 0 = infeasible, 1 = Ck, 2 = All (seed tie-breaking: ties go
    to Ck).  Only the ~O(L) cells the reconstruction visits are recomputed —
    the banded fill stores costs only."""
    if s == t:
        return (2, 0) if np.isfinite(tab.row(s, s)[m]) else (0, 0)
    cand = _c1_candidates(v, tab, tab, s, t, m)
    if m < _m_none(v, s, t):
        cand[:] = _INF32
    k = int(np.argmin(cand))
    best = cand[k]
    choice, sp = (1, s + 1 + k) if np.isfinite(best) else (0, 0)
    if allow_fall:
        c2 = _c2_value(v, tab, s, t, m)
        if c2 < best or (not np.isfinite(best) and np.isfinite(c2)):
            choice, sp, best = 2, 0, c2
    if not np.isfinite(best):
        return 0, 0
    return choice, sp


def choose_offload(v: dict, tb: BandedTable, te: BandedTable,
                   toffP: np.ndarray, tpre32: np.ndarray,
                   s: int, t: int, m: int, bare: bool,
                   allow_fall: bool = True) -> Tuple[int, int]:
    """Branch decision for the offload DP at one cell: choice 0 = infeasible,
    1 = Ck, 2 = All, 3 = Offload (seed tie-breaking: Ck ≺ All ≺ Offload).
    ``toffP`` is the CUM-shifted offload-time vector the fill used
    (``T_off(a^i) + CUM[i]`` in float32)."""
    tab = tb if bare else te
    if s == t:
        return (2, 0) if np.isfinite(tab.row(s, s)[m]) else (0, 0)
    m_none = _m_none(v, s, t)
    cand = _c1_candidates(v, tb, tab, s, t, m)
    if m < m_none:
        cand[:] = _INF32
    k = int(np.argmin(cand))
    best = cand[k]
    choice, sp = (1, s + 1 + k) if np.isfinite(best) else (0, 0)
    if allow_fall:
        c2 = _c2_value(v, te, s, t, m)
        if c2 < best or (not np.isfinite(best) and np.isfinite(c2)):
            choice, sp, best = 2, 0, c2
    if bare and np.isfinite(toffP[s - 1]):
        sps = np.arange(s + 1, t + 1)
        n = len(sps)
        hidden = np.empty(n, dtype=COST_DTYPE)   # CUM-shifted hidden work
        left = np.empty(n, dtype=COST_DTYPE)
        w0 = int(v["WA"][s - 1])
        cum32 = v["CUM_UF"].astype(COST_DTYPE)
        for kk, spp in enumerate(sps):
            hidden[kk] = (_lookup(tb, spp, t, m - int(v["WA"][spp - 1]) + w0)
                          + cum32[spp - 1])
            left[kk] = tb.row(s, spp - 1)[m]
        # X + max(T_off - X, 0) = max(X, T_off), in the CUM-shifted domain;
        # the prefetch charge rides on the left-child companion (Lmb3)
        cand3 = (np.maximum(hidden, toffP[s - 1])
                 + ((left - cum32[s - 1]) + tpre32[s - 1]))
        if m < m_none:
            cand3[:] = _INF32
        k3 = int(np.argmin(cand3))
        if cand3[k3] < best or (not np.isfinite(best)
                                and np.isfinite(cand3[k3])):
            choice, sp, best = 3, s + 1 + k3, cand3[k3]
    if not np.isfinite(best):
        return 0, 0
    return choice, sp
