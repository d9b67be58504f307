"""Deliberately broken Pallas kernels for `repro.check.kernel_analyzer` tests.

This module is ONLY ever parsed (by file path) by the static analyzer — it is
never imported and never executed.  Each kernel mirrors the structure and
naming contract of the shipped ``kernels/dp_fill`` kernels with one seeded
defect:

- ``_racy_fused_kernel``     — the companion rebuild reads the *current*
  band's rows (``off[d]`` instead of ``off[d-1]``), i.e. garbage that no
  earlier grid step has written: a read-before-write race across grid steps.
- ``_oob_fused_kernel``      — the band write lands past the padded row
  margin the driver allocates (``nrows = ncells + 2L + BR``).
- ``_racy_band_kernel``      — a revisited accumulator block with the
  ``j == 0`` initialization missing: the first grid step already reads the
  (uninitialized) output.
- ``_alias_band_kernel``     — correct body, but the driver's output
  BlockSpec index map varies along the innermost grid dimension, so the
  "revisited accumulator" contract is broken (and row tiles alias).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COST_DT = jnp.float32
_INT_CLAMP = 1 << 30


def _shifted_gather(blk, idx, w):
    g = jnp.take_along_axis(blk, jnp.clip(idx, 0, w - 1), axis=1)
    return jnp.where(idx < 0, jnp.float32(jnp.inf), g)


def _racy_fused_kernel(
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
        # BUG: rebuilds companions from band d (this band's own rows, which
        # no grid step has written yet) instead of the finished band d-1.
        start = off_ref[d]
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
            rrow = off_ref[d - 1 - j] + 1 + j + r0
            cand = r_ref[pl.ds(rrow, BR), :] + lm_ref[pl.ds(off_ref[j] + r0, BR), :]
            return jnp.minimum(acc, cand)

        acc = jax.lax.fori_loop(0, d, split, jnp.full((BR, W), inf, COST_DT))
        mn = mn_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
        res = jnp.where(cols < mn, inf, acc)
        t_ref[pl.ds(off_ref[d] + r0, BR), :] = res


def _oob_fused_kernel(
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
            rrow = off_ref[d - 1 - j] + 1 + j + r0
            cand = r_ref[pl.ds(rrow, BR), :] + lm_ref[pl.ds(off_ref[j] + r0, BR), :]
            return jnp.minimum(acc, cand)

        acc = jax.lax.fori_loop(0, d, split, jnp.full((BR, W), inf, COST_DT))
        mn = mn_ref[pl.ds(d - 1, 1), pl.ds(r0, BR)][0][:, None]
        res = jnp.where(cols < mn, inf, acc)
        # BUG: the write escapes the padded row margin (nrows = ncells +
        # 2L + BR); the driver's slack absorbs at most 2L + BR - 1 rows.
        t_ref[pl.ds(off_ref[d] + r0 + 2 * L + BR + 1, BR), :] = res


def _racy_band_kernel(r_ref, lm_ref, o_ref):
    # BUG: no `pl.when(j == 0)` initialization — the first split step
    # already folds the uninitialized accumulator into the result.
    cand = r_ref[0] + lm_ref[0]
    o_ref[...] = jnp.minimum(o_ref[...], cand)


def band_racy(r, lm, *, d, block_rows, w, interpret=False):
    ns_pad = r.shape[1]
    grid = (ns_pad // block_rows, d)
    plane = pl.BlockSpec((1, block_rows, w), lambda i, j: (j, i, 0))
    return pl.pallas_call(
        _racy_band_kernel,
        grid=grid,
        in_specs=[plane, plane],
        out_specs=pl.BlockSpec((block_rows, w), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ns_pad, w), r.dtype),
        interpret=interpret,
    )(r, lm)


def _alias_band_kernel(r_ref, lm_ref, o_ref):
    j = pl.program_id(1)
    cand = r_ref[0] + lm_ref[0]

    @pl.when(j == 0)
    def _set():
        o_ref[...] = cand

    @pl.when(j != 0)
    def _acc():
        o_ref[...] = jnp.minimum(o_ref[...], cand)


def band_alias(r, lm, *, d, block_rows, w, interpret=False):
    ns_pad = r.shape[1]
    grid = (ns_pad // block_rows, d)
    plane = pl.BlockSpec((1, block_rows, w), lambda i, j: (j, i, 0))
    # BUG: the output block origin follows the *innermost* grid dimension,
    # so the accumulator is not revisited (and tiles alias across i).
    return pl.pallas_call(
        _alias_band_kernel,
        grid=grid,
        in_specs=[plane, plane],
        out_specs=pl.BlockSpec((block_rows, w), lambda i, j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((ns_pad, w), r.dtype),
        interpret=interpret,
    )(r, lm)
