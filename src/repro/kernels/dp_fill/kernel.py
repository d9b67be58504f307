"""Length-banded DP band fill — Pallas TPU kernel.

The checkpointing DP's hot path is, per sub-chain length ``d``, a min
reduction over ``d`` split candidates, where the candidate of split offset
``j`` is one elementwise add of two pre-shifted companion-table planes (see
:mod:`repro.core.dp_kernels`):

    cand_j = R[band d-1-j, rows j+1..j+ns] + Lm[band j, rows 1..ns]

The kernel runs that reduction on a grid of ``(row_tiles, d)`` with the
split dimension innermost: each grid step streams one split's
``(block_rows, W)`` companion tiles into VMEM, adds them on the VPU, and
min-accumulates into the output tile (initialized at ``j == 0`` — the
standard revisited-output accumulation pattern; TPU grids iterate the last
dimension sequentially, so the running minimum is race-free).  The offload
variant carries three accumulators (input-bare C1, input-embedded C1, and
the C3 offload plane whose PCIe stall is pre-folded into a
``max(X, T_off)``) so the three-tier fill costs one extra pass over the same
tiles rather than three kernels.

Exactness: every operation is an f32 add / min / max of the same operand
pairs the numpy banded fill uses, and IEEE min/max do not round — on chains
whose quantities are exactly representable in f32 the result is bit-equal to
``impl="banded"`` in any evaluation order (asserted by
``tests/test_dp_fill_pallas.py``).

Two kernel families live here:

- the **per-band** kernels (``band_min_two_tier`` / ``band_min_offload``,
  ``impl="pallas"``): the driver in :mod:`.ops` stages one band per call and
  rebuilds companion tables on the host between bands — O(L) dispatches and
  host↔device round-trips per fill;
- the **fused** kernels (``fused_fill_two_tier`` / ``fused_fill_offload``,
  ``impl="pallas_fused"``): ONE ``pallas_call`` runs the entire band
  recursion device-side on a ``(L, row_tiles)`` grid (both dimensions iterate
  sequentially on TPU, ``row_tiles`` innermost).  The cost table(s) and the
  companion tables ``R``/``Lm`` are revisited whole-array output blocks that
  persist across grid steps; at each band's first row tile the companions of
  the just-written band are rebuilt *in-kernel* (per-row shift via a
  clamped ``take_along_axis`` gather plus the ``CUM32`` bake-in), so the host
  never re-publishes anything mid-fill.  Buffers are sized by the
  ``O(cap_d)`` saturation bound of
  :func:`repro.core.dp_kernels.saturation_caps` — the column width is the
  widest unsaturated band, not ``S + 1`` — and the saturated tail is
  broadcast once on the host after the single dispatch.  ``block_rows``
  picks the row-tile height (see :mod:`.autotune`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COST_DT = jnp.float32

#: Rows per VMEM tile.  At the default S=500 discretization a (256, 501) f32
#: tile is ~0.5 MB; with two inputs and one output per step (five inputs and
#: three outputs for the offload variant) the working set stays well under
#: the ~16 MB VMEM budget.
DEFAULT_BLOCK_ROWS = 256


def _pad_rows(x: jnp.ndarray, rows: int, value: float) -> jnp.ndarray:
    pad = rows - x.shape[1]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)), constant_values=value)


def _band_min_kernel(r_ref, lm_ref, o_ref):
    j = pl.program_id(1)
    cand = r_ref[0] + lm_ref[0]

    @pl.when(j == 0)
    def _():
        o_ref[...] = cand

    @pl.when(j != 0)
    def _():
        o_ref[...] = jnp.minimum(o_ref[...], cand)


def band_min_two_tier(
    r: jax.Array,
    lm: jax.Array,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Two-tier C1 reduction: ``min_j (r[j] + lm[j])``.

    ``r``/``lm``: ``(d, ns, W)`` stacked per-split companion planes (``r``
    pre-shifted by the split's memory cost, ``+inf`` where out of budget).
    Returns the ``(ns, W)`` running minimum.
    """
    d, ns, w = r.shape
    block_rows = min(block_rows, ns)
    ns_pad = pl.cdiv(ns, block_rows) * block_rows
    r = _pad_rows(r, ns_pad, jnp.inf)
    lm = _pad_rows(lm, ns_pad, 0.0)
    grid = (ns_pad // block_rows, d)
    out = pl.pallas_call(
        _band_min_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_rows, w), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, block_rows, w), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, w), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ns_pad, w), r.dtype),
        interpret=interpret,
    )(r, lm)
    return out[:ns]


def _band_min_offload_kernel(
    r_ref, r3_ref, lmb_ref, lme_ref, lmb3_ref, toff_ref, ob_ref, oe_ref, o3_ref
):
    j = pl.program_id(1)
    r = r_ref[0]
    cb = r + lmb_ref[0]
    ce = r + lme_ref[0]
    # C3: X + max(T_off - X, 0) = max(X, T_off); the prefetch charge is
    # pre-added to the left-child companion lmb3
    c3 = jnp.maximum(r3_ref[0], toff_ref[...]) + lmb3_ref[0]

    @pl.when(j == 0)
    def _():
        ob_ref[...] = cb
        oe_ref[...] = ce
        o3_ref[...] = c3

    @pl.when(j != 0)
    def _():
        ob_ref[...] = jnp.minimum(ob_ref[...], cb)
        oe_ref[...] = jnp.minimum(oe_ref[...], ce)
        o3_ref[...] = jnp.minimum(o3_ref[...], c3)


def band_min_offload(
    r: jax.Array,
    r3: jax.Array,
    lmb: jax.Array,
    lme: jax.Array,
    lmb3: jax.Array,
    toff: jax.Array,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Offload-band reduction: three accumulators over the same split loop.

    ``r``: shared pre-shifted right-child planes (C1, both input states);
    ``r3``: the C3 right-child planes read at the parent-side column offset
    (hidden work ``X`` in the CUM-shifted domain); ``lmb``/``lme``/``lmb3``:
    left-child companions (bare / embedded / bare-with-prefetch-charge);
    ``toff``: ``(ns, 1)`` CUM-shifted offload times.  Returns
    ``(min C1_bare, min C1_embedded, min C3)``, each ``(ns, W)``.
    """
    d, ns, w = r.shape
    block_rows = min(block_rows, ns)
    ns_pad = pl.cdiv(ns, block_rows) * block_rows
    r = _pad_rows(r, ns_pad, jnp.inf)
    r3 = _pad_rows(r3, ns_pad, jnp.inf)
    lmb = _pad_rows(lmb, ns_pad, 0.0)
    lme = _pad_rows(lme, ns_pad, 0.0)
    lmb3 = _pad_rows(lmb3, ns_pad, 0.0)
    pad = ns_pad - toff.shape[0]
    if pad:
        toff = jnp.pad(toff, ((0, pad), (0, 0)))
    grid = (ns_pad // block_rows, d)
    plane = pl.BlockSpec((1, block_rows, w), lambda i, j: (j, i, 0))
    out = pl.BlockSpec((block_rows, w), lambda i, j: (i, 0))
    shape = jax.ShapeDtypeStruct((ns_pad, w), r.dtype)
    ob, oe, o3 = pl.pallas_call(
        _band_min_offload_kernel,
        grid=grid,
        in_specs=[
            plane,
            plane,
            plane,
            plane,
            plane,
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[out, out, out],
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(r, r3, lmb, lme, lmb3, toff)
    return ob[:ns], oe[:ns], o3[:ns]


# ---------------------------------------------------------------------------
# Fused device-resident fill (impl="pallas_fused") — one pallas_call per fill
# ---------------------------------------------------------------------------

_INT_CLAMP = 1 << 30  # matches _FillCtx.raw_wa's int32-overflow clamp


def _whole(x: jnp.ndarray) -> pl.BlockSpec:
    """Whole-array block revisited at every grid step (index_map constant) —
    the buffer persists across the sequential band recursion."""
    nd = x.ndim if hasattr(x, "ndim") else len(x.shape)
    return pl.BlockSpec(tuple(x.shape), lambda d, i, _n=nd: (0,) * _n)


def _shifted_gather(blk, idx, w):
    """``out[r, c] = blk[r, idx[r, c]]`` with ``idx < 0`` reading ``+inf``
    (the sentinel semantics) and reads beyond the buffer clamping to the
    last stored column (equal to column ``S`` by the saturation invariant)."""
    g = jnp.take_along_axis(blk, jnp.clip(idx, 0, w - 1), axis=1)
    return jnp.where(idx < 0, jnp.float32(jnp.inf), g)


def _fused_two_tier_kernel(
    t0_ref,
    off_ref,
    wa_ref,
    wb_ref,
    cum_ref,
    uf_ref,
    ub_ref,
    mn_ref,
    ma_ref,
    t_ref,
    r_ref,
    lm_ref,
    *,
    L,
    W,
    BR,
    allow_fall,
):
    d = pl.program_id(0) + 1
    i = pl.program_id(1)
    r0 = i * BR
    ns = L + 1 - d
    NS0 = L + 1
    inf = jnp.float32(jnp.inf)

    @pl.when((d == 1) & (i == 0))
    def _init():
        t_ref[...] = t0_ref[...]

    @pl.when(i == 0)
    def _rebuild():
        # companions of the just-written band d-1 (rows beyond that band are
        # overwritten with garbage here, and rewritten by their own band's
        # rebuild before any read — see the ops driver for the argument)
        start = off_ref[d - 1]
        blk = t_ref[pl.ds(start, NS0), :]
        cum = cum_ref[pl.ds(0, NS0)][:, None]
        cols = jax.lax.broadcasted_iota(jnp.int32, (NS0, W), 1)
        idx = cols - wa_ref[pl.ds(0, NS0)][:, None]
        r_ref[pl.ds(start, NS0), :] = _shifted_gather(blk, idx, W) + cum
        lm_ref[pl.ds(start, NS0), :] = blk - cum

    @pl.when(r0 < ns)
    def _compute():
        cols = jax.lax.broadcasted_iota(jnp.int32, (BR, W), 1)

        def split(j, acc):
            # split sp = s + 1 + j: right child rows of band d-1-j, left
            # child rows of band j — both plain pre-shifted companion reads
            rrow = off_ref[d - 1 - j] + 1 + j + r0
            cand = r_ref[pl.ds(rrow, BR), :] + lm_ref[pl.ds(off_ref[j] + r0, BR), :]
            return jnp.minimum(acc, cand)

        acc = jax.lax.fori_loop(0, d, split, jnp.full((BR, W), inf, COST_DT))
        mn = mn_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
        res = jnp.where(cols < mn, inf, acc)
        if allow_fall:
            # C2: u_f^s + C[s+1, t][m - wabar^s] + u_b^s, masked by m_all
            blk = t_ref[pl.ds(off_ref[d - 1] + 1 + r0, BR), :]
            idx = cols - wb_ref[pl.ds(1 + r0, BR)][:, None]
            uf = uf_ref[pl.ds(1 + r0, BR)][:, None]
            ub = ub_ref[pl.ds(1 + r0, BR)][:, None]
            c2 = (_shifted_gather(blk, idx, W) + uf) + ub
            ma = ma_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
            res = jnp.minimum(res, jnp.where(cols < ma, inf, c2))
        t_ref[pl.ds(off_ref[d] + r0, BR), :] = res


def fused_fill_two_tier(
    t0,
    off,
    wa,
    wb,
    cum,
    uf,
    ub,
    mn,
    ma,
    *,
    L,
    W,
    block_rows,
    allow_fall,
    interpret=False,
):
    """Single-dispatch two-tier band fill.

    ``t0``: ``(nrows, W)`` initial table — the base-case band at rows
    ``off[0]..off[1])``, ``+inf`` elsewhere (``nrows`` is padded past the
    cell count so every dynamically-sliced tile stays in bounds; see the
    ops driver).  Integer operands are pre-clamped int32 (the caller mirrors
    ``_FillCtx``'s ``1 << 30`` overflow clamp).  Returns the filled table;
    the ``R``/``Lm`` companion buffers are device scratch published as
    outputs only because revisited output blocks are the one Pallas buffer
    kind guaranteed to persist across grid steps.
    """
    NSMAX = max(L, 1)
    BR = max(1, min(block_rows, NSMAX))
    grid = (L, pl.cdiv(NSMAX, BR))
    shape = jax.ShapeDtypeStruct(t0.shape, t0.dtype)
    kernel_fn = functools.partial(
        _fused_two_tier_kernel, L=L, W=W, BR=BR, allow_fall=allow_fall
    )
    t, _, _ = pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=[_whole(x) for x in (t0, off, wa, wb, cum, uf, ub, mn, ma)],
        out_specs=[_whole(t0)] * 3,
        out_shape=[shape, shape, shape],
        interpret=interpret,
    )(t0, off, wa, wb, cum, uf, ub, mn, ma)
    return t


def _fused_offload_kernel(
    t0b_ref,
    t0e_ref,
    off_ref,
    wa_ref,
    wb_ref,
    cum_ref,
    uf_ref,
    ub_ref,
    mn_ref,
    ma_ref,
    toff_ref,
    tpre_ref,
    tb_ref,
    te_ref,
    r_ref,
    lmb_ref,
    lme_ref,
    lmb3_ref,
    *,
    L,
    W,
    BR,
    allow_fall,
    host_on,
):
    d = pl.program_id(0) + 1
    i = pl.program_id(1)
    r0 = i * BR
    ns = L + 1 - d
    NS0 = L + 1
    inf = jnp.float32(jnp.inf)

    @pl.when((d == 1) & (i == 0))
    def _init():
        tb_ref[...] = t0b_ref[...]
        te_ref[...] = t0e_ref[...]

    @pl.when(i == 0)
    def _rebuild():
        start = off_ref[d - 1]
        blkb = tb_ref[pl.ds(start, NS0), :]
        blke = te_ref[pl.ds(start, NS0), :]
        cum = cum_ref[pl.ds(0, NS0)][:, None]
        cols = jax.lax.broadcasted_iota(jnp.int32, (NS0, W), 1)
        idx = cols - wa_ref[pl.ds(0, NS0)][:, None]
        r_ref[pl.ds(start, NS0), :] = _shifted_gather(blkb, idx, W) + cum
        lmb = blkb - cum
        lmb_ref[pl.ds(start, NS0), :] = lmb
        lme_ref[pl.ds(start, NS0), :] = blke - cum
        if host_on:
            # C3 left companion with the prefetch charge pre-added
            lmb3_ref[pl.ds(start, NS0), :] = lmb + tpre_ref[pl.ds(0, NS0)][:, None]

    @pl.when(r0 < ns)
    def _compute():
        cols = jax.lax.broadcasted_iota(jnp.int32, (BR, W), 1)
        wa_s = wa_ref[pl.ds(r0, BR)][:, None]  # WA[s-1], s = r0+rr+1
        toff = toff_ref[pl.ds(r0, BR)][:, None]

        def split(j, accs):
            accb, acce, acc3 = accs
            rrow = off_ref[d - 1 - j] + 1 + j + r0
            lrow = off_ref[j] + r0
            r = r_ref[pl.ds(rrow, BR), :]
            accb = jnp.minimum(accb, r + lmb_ref[pl.ds(lrow, BR), :])
            acce = jnp.minimum(acce, r + lme_ref[pl.ds(lrow, BR), :])
            if host_on:
                # C3 right segment: the offloaded input's slots are
                # reclaimed, so the shift is WA[sp-1] - WA[s-1]; the clamp
                # ladder mirrors _FillCtx.raw_wa (int32-safe, clip to S,
                # sentinel below 0) and the PCIe stall folds into the max
                blkb = tb_ref[pl.ds(rrow, BR), :]
                wa_sp = wa_ref[pl.ds(1 + j + r0, BR)][:, None]
                raw = jnp.clip(cols - wa_sp, -_INT_CLAMP, W - 1)
                idx3 = jnp.clip(raw + wa_s, -1, W - 1)
                c3 = _shifted_gather(blkb, idx3, W)
                c3 = c3 + cum_ref[pl.ds(1 + j + r0, BR)][:, None]
                c3 = jnp.maximum(c3, toff)
                c3 = c3 + lmb3_ref[pl.ds(lrow, BR), :]
                acc3 = jnp.minimum(acc3, c3)
            return accb, acce, acc3

        start_acc = jnp.full((BR, W), inf, COST_DT)
        accb, acce, acc3 = jax.lax.fori_loop(
            0, d, split, (start_acc, start_acc, start_acc)
        )
        mn = mn_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
        infeas = cols < mn
        resb = jnp.where(infeas, inf, accb)
        rese = jnp.where(infeas, inf, acce)
        if allow_fall:
            # C2 child is embedded: gather from the Ce table
            blk = te_ref[pl.ds(off_ref[d - 1] + 1 + r0, BR), :]
            idx = cols - wb_ref[pl.ds(1 + r0, BR)][:, None]
            uf = uf_ref[pl.ds(1 + r0, BR)][:, None]
            ub = ub_ref[pl.ds(1 + r0, BR)][:, None]
            c2 = (_shifted_gather(blk, idx, W) + uf) + ub
            ma = ma_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
            c2 = jnp.where(cols < ma, inf, c2)
            resb = jnp.minimum(resb, c2)
            rese = jnp.minimum(rese, c2)
        if host_on:
            resb = jnp.minimum(resb, jnp.where(infeas, inf, acc3))
        tb_ref[pl.ds(off_ref[d] + r0, BR), :] = resb
        te_ref[pl.ds(off_ref[d] + r0, BR), :] = rese


def fused_fill_offload(
    t0b,
    t0e,
    off,
    wa,
    wb,
    cum,
    uf,
    ub,
    mn,
    ma,
    toff,
    tpre,
    *,
    L,
    W,
    block_rows,
    allow_fall,
    host_on,
    interpret=False,
):
    """Single-dispatch offload (three-tier) band fill: two cost tables and
    four companion buffers carried device-side, the C3 stall pre-folded to
    ``max(X, T_off)`` — returns ``(Cb, Ce)`` filled tables."""
    NSMAX = max(L, 1)
    BR = max(1, min(block_rows, NSMAX))
    grid = (L, pl.cdiv(NSMAX, BR))
    shape = jax.ShapeDtypeStruct(t0b.shape, t0b.dtype)
    kernel_fn = functools.partial(
        _fused_offload_kernel, L=L, W=W, BR=BR, allow_fall=allow_fall, host_on=host_on
    )
    ins = (t0b, t0e, off, wa, wb, cum, uf, ub, mn, ma, toff, tpre)
    tb, te, _, _, _, _ = pl.pallas_call(
        kernel_fn,
        grid=grid,
        in_specs=[_whole(x) for x in ins],
        out_specs=[_whole(t0b)] * 6,
        out_shape=[shape] * 6,
        interpret=interpret,
    )(*ins)
    return tb, te
