"""Band-fill drivers for ``impl="pallas"`` / ``impl="pallas_fused"`` — the
solver-side dispatch seam.

These mirror the numpy banded fills of :mod:`repro.core.dp_kernels` exactly
(same companion tables, same thresholds, same saturated m-column pruning,
same C2 fall plane) but hand the DP's hot loop to the Pallas kernels in
:mod:`.kernel`:

- ``fill_two_tier`` / ``fill_offload`` (``impl="pallas"``) keep the band
  recursion on the host — companion tables are republished after each band,
  one kernel launch per length (O(L) dispatches per fill);
- ``fill_two_tier_fused`` / ``fill_offload_fused`` (``impl="pallas_fused"``)
  stage the *whole* recursion as ONE ``pallas_call``: the host builds the
  base case, thresholds, and clamped integer operands, dispatches once, and
  unpacks the returned table(s) — companion rebuild happens in-kernel, and
  the device buffers are sized by the ``O(cap_d)`` saturation bound (the
  widest unsaturated band), with the saturated tail broadcast on the host
  after the fact.  ``block_rows`` (the row-tile height) resolves through
  :mod:`.autotune` when not given.

Dispatch seam: the kernels run compiled, which needs a TPU backend.
Interpret mode (kernel bodies executed in Python, on any backend) runs only
when :func:`set_interpret` asks for it — the CPU tests and the CPU bench rows
do; a compiled dispatch off a TPU raises instead of quietly interpreting.
"""

from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import numpy as np

from ...core import dp_kernels
from ...core.dp_kernels import (
    COST_DTYPE,
    INFEASIBLE,
    BandedTable,
    _build_lm_band,
    _build_r_band,
    _fall_plane,
    _FillCtx,
    _INF32,
    _views,
)
from . import kernel

_INTERPRET: list = [False]


def set_interpret(flag: bool) -> None:
    """``True`` runs the kernels in Pallas interpret mode; ``False`` (the
    default) dispatches them compiled."""
    _INTERPRET[0] = bool(flag)


@contextlib.contextmanager
def interpreting(flag: bool = True):
    """:func:`set_interpret` for the enclosed fills only; the previous
    setting is restored on exit."""
    previous = _INTERPRET[0]
    set_interpret(flag)
    try:
        yield
    finally:
        set_interpret(previous)


def interpret_mode() -> bool:
    """Whether the fills interpret the kernels.  Raises when compiled
    dispatch is asked for on a backend that is not a TPU."""
    if _INTERPRET[0]:
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"the Pallas DP fills (impl='pallas' / 'pallas_fused') compile "
            f"for a TPU, but JAX's backend is {backend!r}; call "
            f"repro.kernels.dp_fill.ops.set_interpret(True) to run them in "
            f"interpret mode")
    return False


def fill_two_tier(dchain, S: int, allow_fall: bool = True,
                  v: Optional[dict] = None,
                  prune: Optional[bool] = None) -> BandedTable:
    """Two-tier band fill with the split reduction on the Pallas kernel.
    Band-exact against :func:`repro.core.dp_kernels.fill_two_tier` on
    f32-exact chains (same adds, same mins — IEEE min does not round)."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tab = BandedTable(L, S)
    ctx.base_case(tab)
    caps = (dp_kernels.saturation_caps(v, S, allow_fall)
            if dp_kernels._resolve_prune(prune) else None)
    interpret = interpret_mode()
    S1 = ctx.S1
    off = tab.off
    R = np.full((int(off[-1]), S1), INFEASIBLE, dtype=COST_DTYPE)
    Lm = np.empty((int(off[-1]), S1), dtype=COST_DTYPE)
    _build_r_band(ctx, R, tab, 0, clamp_tail=False)
    _build_lm_band(ctx, Lm, tab, 0)
    for d in range(1, L + 1):
        ns = L + 1 - d
        W = dp_kernels.band_width(caps, d, S)
        ma, mn = ctx.thresholds(d)
        # stack the d split planes for this band; the kernel min-reduces them
        rs = np.empty((d, ns, W), dtype=COST_DTYPE)
        ls = np.empty((d, ns, W), dtype=COST_DTYPE)
        for j in range(d):                  # split sp = s + 1 + j
            base = int(off[d - 1 - j]) + 1 + j
            rs[j] = R[base:base + ns, :W]
            ls[j] = Lm[off[j]:off[j] + ns, :W]
        resfull = tab.band(d)[:, 1:]
        res = resfull[:, :W]
        res[:] = np.asarray(
            kernel.band_min_two_tier(rs, ls, interpret=interpret))
        res[ctx.ms[None, :W] < mn[:, None]] = _INF32
        if allow_fall:
            c2 = np.empty((ns, W), dtype=COST_DTYPE)
            _fall_plane(ctx, tab, d, ns, ma, c2)
            np.minimum(res, c2, out=res)
        if W <= S:
            resfull[:, W:] = resfull[:, W - 1:W]   # saturated tail
        _build_r_band(ctx, R, tab, d, clamp_tail=False)
        _build_lm_band(ctx, Lm, tab, d)
    return tab


def fill_offload(dchain, S: int, allow_fall: bool = True,
                 v: Optional[dict] = None, prune: Optional[bool] = None
                 ) -> Tuple[BandedTable, BandedTable]:
    """Offload (three-tier) band fill on the Pallas kernel: the C3 stall is
    folded into the kernel's ``max(X, T_off)`` and all three accumulators
    ride one pass over the split planes."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tb, te = BandedTable(L, S), BandedTable(L, S)
    ctx.base_case(tb)
    ctx.base_case(te)
    caps = (dp_kernels.saturation_caps(v, S, allow_fall)
            if dp_kernels._resolve_prune(prune) else None)
    interpret = interpret_mode()
    host = dchain.chain.host
    host_on = host is not None and host.enabled
    tpre32 = dchain.chain.prefetch_times().astype(COST_DTYPE)
    S1, S2 = ctx.S1, ctx.S2
    flat_b = tb.data.reshape(-1)
    offb = tb.off
    slice_c3 = host_on and ctx.wa_uncapped
    ncells = int(offb[-1])
    R = np.full((ncells, S1 + (ctx.wcap if slice_c3 else 0)),
                INFEASIBLE, dtype=COST_DTYPE)
    Lmb = np.empty((ncells, S1), dtype=COST_DTYPE)
    Lme = np.empty((ncells, S1), dtype=COST_DTYPE)
    Lmb3 = np.empty((ncells, S1), dtype=COST_DTYPE) if host_on else None
    _build_r_band(ctx, R, tb, 0, clamp_tail=slice_c3)
    _build_lm_band(ctx, Lmb, tb, 0)
    _build_lm_band(ctx, Lme, te, 0)
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
        W = dp_kernels.band_width(caps, d, S)
        ma, mn = ctx.thresholds(d)
        rs = np.empty((d, ns, W), dtype=COST_DTYPE)
        lbs = np.empty((d, ns, W), dtype=COST_DTYPE)
        les = np.empty((d, ns, W), dtype=COST_DTYPE)
        if host_on:
            r3s = np.empty((d, ns, W), dtype=COST_DTYPE)
            lb3s = np.empty((d, ns, W), dtype=COST_DTYPE)
            wacol = ctx.WA[:ns].astype(np.int32)[:, None]
            par_groups = [(w, ps[:np.searchsorted(ps, ns)])
                          for w, ps in ctx.groups]
            ifi = np.empty((ns, W), dtype=np.int32)
        for j in range(d):                  # split sp = s + 1 + j
            base = int(offb[d - 1 - j]) + 1 + j
            lo = int(offb[j])
            rs[j] = R[base:base + ns, :W]
            lbs[j] = Lmb[lo:lo + ns, :W]
            les[j] = Lme[lo:lo + ns, :W]
            if not host_on:
                continue
            lb3s[j] = Lmb3[lo:lo + ns, :W]
            # C3 right plane: R read at the parent-side column offset
            # WA[s-1] (slots of the offloaded input reclaimed); the kernel
            # folds the stall max on top
            if slice_c3:
                Rblk = R[base:base + ns]
                for w0, rows in par_groups:
                    if len(rows):
                        r3s[j, rows] = Rblk[rows, w0:w0 + W]
            else:
                np.add(ctx.raw_wa[1 + j:1 + j + ns, :W], wacol, out=ifi)
                np.clip(ifi, -1, S, out=ifi)
                ifi += 1
                ifi += ctx.is2[:ns, None]
                np.take(flat_b[base * S2:], ifi, out=r3s[j])
                r3s[j] += ctx.CUM32[1 + j:1 + j + ns, None]
        resb_full = tb.band(d)[:, 1:]
        rese_full = te.band(d)[:, 1:]
        resb = resb_full[:, :W]
        rese = rese_full[:, :W]
        if host_on:
            ob, oe, o3 = kernel.band_min_offload(
                rs, r3s, lbs, les, lb3s, toffP[:ns, None],
                interpret=interpret)
            resb[:] = np.asarray(ob)
            rese[:] = np.asarray(oe)
            c3acc = np.array(o3)        # writable copy (the mask edits it)
        else:
            resb[:] = np.asarray(
                kernel.band_min_two_tier(rs, lbs, interpret=interpret))
            rese[:] = np.asarray(
                kernel.band_min_two_tier(rs, les, interpret=interpret))
            c3acc = None
        infeas = ctx.ms[None, :W] < mn[:, None]
        resb[infeas] = _INF32
        rese[infeas] = _INF32
        if allow_fall:
            c2 = np.empty((ns, W), dtype=COST_DTYPE)
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
# Fused single-dispatch fills (impl="pallas_fused")
# ---------------------------------------------------------------------------

_ICLAMP = kernel._INT_CLAMP


class _FusedOperands:
    """Host-side staging for the fused kernels: the padded initial table,
    the band offsets, the clamped integer vectors, and the per-band
    thresholds — everything the recursion needs, computed before the single
    dispatch.

    Row padding: every in-kernel tile is a *static*-height dynamic slice, so
    the padded lanes of small bands read/write rows past the band.  Those
    rows always belong to later bands (or to this pad margin) and are
    rewritten by their own band's step before any read, so garbage there is
    harmless — the pad only has to keep the slices in bounds:
    ``2L + block_rows`` rows cover the deepest read
    (``off[d-1-j] + 1 + j + row_tiles·BR``).

    Width: ``W`` is the widest unsaturated band
    (:func:`repro.core.dp_kernels.band_width` at ``d = L`` — the caps are
    monotone), i.e. the ``O(cap_d)`` VMEM sizing bound.  Columns the banded
    fill would broadcast are computed directly in-kernel; by the saturation
    invariant the values are bit-identical, so the host-side unpack can
    broadcast the ``[W, S]`` tail from column ``W - 1``.
    """

    def __init__(self, ctx, caps, BR: int):
        L, S = ctx.L, ctx.S
        self.L, self.S = L, S
        self.W = dp_kernels.band_width(caps, L, S)
        sizes = np.array([L + 1 - d for d in range(L + 1)], dtype=np.int64)
        off = np.concatenate([[0], np.cumsum(sizes)])
        self.ncells = int(off[-1])
        self.nrows = self.ncells + 2 * L + BR
        self.off = off.astype(np.int32)
        vec = 2 * L + BR + 2

        def pad_to(a, n, fill=0):
            out = np.full(n, fill, dtype=a.dtype)
            out[: len(a)] = a
            return out

        self.wa = pad_to(np.clip(ctx.WA, 0, _ICLAMP).astype(np.int32), vec)
        self.wb = pad_to(np.clip(ctx.WB, 0, _ICLAMP).astype(np.int32), vec)
        self.cum = pad_to(ctx.CUM32, vec)
        self.uf = pad_to(ctx.UF32, vec)
        self.ub = pad_to(ctx.UB32, vec)
        rt = -(-max(L, 1) // BR)
        self.mn = np.zeros((max(L, 1), rt * BR), dtype=np.int32)
        self.ma = np.zeros((max(L, 1), rt * BR), dtype=np.int32)
        for d in range(1, L + 1):
            ma_d, mn_d = ctx.thresholds(d)
            ns = L + 1 - d
            self.mn[d - 1, :ns] = np.clip(mn_d, 0, _ICLAMP)
            self.ma[d - 1, :ns] = np.clip(ma_d, 0, _ICLAMP)
        self.vec = vec

    def initial_table(self, tab: BandedTable) -> np.ndarray:
        t0 = np.full((self.nrows, self.W), INFEASIBLE, dtype=COST_DTYPE)
        t0[: self.ncells] = tab.data[:, 1 : 1 + self.W]
        return t0

    def unpack(self, dev, tab: BandedTable) -> BandedTable:
        W, S = self.W, self.S
        tab.data[:, 1 : 1 + W] = np.asarray(dev)[: self.ncells]
        if W <= S:
            tab.data[:, 1 + W :] = tab.data[:, W : W + 1]  # saturated tail
        return tab


def _resolve_block_rows(block_rows, L: int, S: int, interpret: bool) -> int:
    if block_rows is not None:
        return int(block_rows)
    from . import autotune
    return autotune.resolve_block_rows(L, S, interpret=interpret)


def fill_two_tier_fused(dchain, S: int, allow_fall: bool = True,
                        v: Optional[dict] = None, prune: Optional[bool] = None,
                        block_rows: Optional[int] = None) -> BandedTable:
    """Two-tier band fill in ONE device dispatch: the entire band recursion
    (split reduction, thresholds, C2 fall plane, companion rebuild) runs
    inside a single ``pallas_call`` — no per-band host loop.  Band-exact
    against :func:`repro.core.dp_kernels.fill_two_tier` on f32-exact
    chains."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tab = BandedTable(L, S)
    ctx.base_case(tab)
    if L == 0:
        return tab
    caps = (dp_kernels.saturation_caps(v, S, allow_fall)
            if dp_kernels._resolve_prune(prune) else None)
    interpret = interpret_mode()
    BR = max(1, min(_resolve_block_rows(block_rows, L, S, interpret), L))
    ops_ = _FusedOperands(ctx, caps, BR)
    dev = kernel.fused_fill_two_tier(
        ops_.initial_table(tab), ops_.off, ops_.wa, ops_.wb, ops_.cum,
        ops_.uf, ops_.ub, ops_.mn, ops_.ma, L=L, W=ops_.W, block_rows=BR,
        allow_fall=allow_fall, interpret=interpret)
    return ops_.unpack(dev, tab)


def fill_offload_fused(dchain, S: int, allow_fall: bool = True,
                       v: Optional[dict] = None, prune: Optional[bool] = None,
                       block_rows: Optional[int] = None
                       ) -> Tuple[BandedTable, BandedTable]:
    """Offload (three-tier) band fill in ONE device dispatch: both cost
    tables and all four companion buffers stay device-resident across the
    whole recursion, the C3 stall folded to ``max(X, T_off)`` in-kernel."""
    if v is None:
        v = _views(dchain)
    L = dchain.length
    ctx = _FillCtx(v, L, S)
    tb, te = BandedTable(L, S), BandedTable(L, S)
    ctx.base_case(tb)
    ctx.base_case(te)
    if L == 0:
        return tb, te
    caps = (dp_kernels.saturation_caps(v, S, allow_fall)
            if dp_kernels._resolve_prune(prune) else None)
    interpret = interpret_mode()
    BR = max(1, min(_resolve_block_rows(block_rows, L, S, interpret), L))
    ops_ = _FusedOperands(ctx, caps, BR)
    host = dchain.chain.host
    host_on = host is not None and host.enabled
    if host_on:
        toff = (dchain.chain.offload_times()
                + np.asarray(v["CUM_UF"][:L + 1])).astype(COST_DTYPE)
        tpre = dchain.chain.prefetch_times().astype(COST_DTYPE)
    else:
        toff = np.zeros(L + 1, dtype=COST_DTYPE)
        tpre = np.zeros(L + 1, dtype=COST_DTYPE)
    pad = np.zeros(ops_.vec, dtype=COST_DTYPE)
    toff_p, tpre_p = pad.copy(), pad.copy()
    toff_p[: L + 1], tpre_p[: L + 1] = toff, tpre
    devb, deve = kernel.fused_fill_offload(
        ops_.initial_table(tb), ops_.initial_table(te), ops_.off, ops_.wa,
        ops_.wb, ops_.cum, ops_.uf, ops_.ub, ops_.mn, ops_.ma, toff_p,
        tpre_p, L=L, W=ops_.W, block_rows=BR, allow_fall=allow_fall,
        host_on=host_on, interpret=interpret)
    ops_.unpack(devb, tb)
    ops_.unpack(deve, te)
    return tb, te
