"""Cross-entropy from hidden states that never materializes the full
(B,S,V) logits tensor — the single largest activation for 150k-vocab models.

``token_chunked_xent`` (the model's head): a ``lax.scan`` over token blocks,
each against the whole (contiguous) vocabulary.  When the loss is
differentiated, the same scan also computes the gradient: each block's
logits are made once, and ``dh``/``dW`` come from them in that one pass, so
the backward only scales the two by the incoming cotangent.  The loss is the
chain's last stage and its cotangent a scalar, so nothing the backward needs
is unknown in the forward.  Peak memory is O(block·V).

``blockwise_xent`` (vocab-chunked): a ``lax.scan`` over vocab blocks
maintaining a running (max, sum-exp, gold-logit) triple; its backward
(custom VJP) is a second scan recomputing each logits block and
accumulating ``dh``/``dW`` — so peak memory is O(B·S·block) instead of
O(B·S·V).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _lse_scan(h2: jax.Array, w: jax.Array, labels1: jax.Array, block: int):
    """h2: (T,d), w: (d,V), labels1: (T,). Returns (lse (T,), gold (T,))."""
    T, d = h2.shape
    V = w.shape[1]
    nb = -(-V // block)
    Vp = nb * block
    wp = jnp.pad(w, ((0, 0), (0, Vp - V))) if Vp != V else w
    wb = wp.reshape(d, nb, block).transpose(1, 0, 2)        # (nb, d, block)

    def step(carry, inp):
        m, s, gold = carry
        wblk, j = inp
        logits = (h2 @ wblk.astype(h2.dtype)).astype(jnp.float32)  # (T, blk)
        col = j * block + jnp.arange(block)
        logits = jnp.where(col[None, :] < V, logits, -jnp.inf)
        bm = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, bm)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        hit = (labels1[:, None] == col[None, :])
        gold = gold + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return (m_new, s, gold), None

    init = (jnp.full((T,), -jnp.inf, jnp.float32),
            jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.float32))
    (m, s, gold), _ = jax.lax.scan(step, init, (wb, jnp.arange(nb)))
    return m + jnp.log(s), gold


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def blockwise_xent(h: jax.Array, w: jax.Array, labels: jax.Array,
                   mask=None, block: int = 8192, z_loss: float = 0.0
                   ) -> jax.Array:
    loss, _ = _value_aux(h, w, labels, mask, block, z_loss)
    return loss


def _value_aux(h, w, labels, mask, block, z_loss):
    B, S, d = h.shape
    T = B * S
    h2 = h.reshape(T, d)
    labels1 = labels.reshape(T)
    lse, gold = _lse_scan(h2, w, labels1, block)
    per_tok = lse - gold
    if z_loss:
        per_tok = per_tok + z_loss * lse ** 2
    if mask is not None:
        m1 = mask.reshape(T).astype(jnp.float32)
        denom = jnp.maximum(m1.sum(), 1.0)
        loss = (per_tok * m1).sum() / denom
        wgt = m1 / denom
    else:
        loss = per_tok.mean()
        wgt = jnp.full((T,), 1.0 / T, jnp.float32)
    return loss, (lse, wgt)


def _fwd(h, w, labels, mask, block, z_loss):
    loss, (lse, wgt) = _value_aux(h, w, labels, mask, block, z_loss)
    return loss, (h, w, labels, mask, lse, wgt)


def _bwd(block, z_loss, res, g):
    import numpy as np

    h, w, labels, mask, lse, wgt = res
    B, S, d = h.shape
    T = B * S
    h2 = h.reshape(T, d)
    labels1 = labels.reshape(T)
    V = w.shape[1]
    nb = -(-V // block)
    Vp = nb * block
    wp = jnp.pad(w, ((0, 0), (0, Vp - V))) if Vp != V else w
    wb = wp.reshape(d, nb, block).transpose(1, 0, 2)
    coef = (g * wgt).astype(jnp.float32)                     # (T,)
    zcoef = (jnp.ones_like(lse) + 2.0 * z_loss * lse if z_loss
             else jnp.ones_like(lse))

    def step(dh, inp):
        wblk, j = inp
        logits = (h2 @ wblk.astype(h2.dtype)).astype(jnp.float32)
        col = j * block + jnp.arange(block)
        valid = col[None, :] < V
        p = jnp.where(valid, jnp.exp(logits - lse[:, None]), 0.0)
        hit = (labels1[:, None] == col[None, :]).astype(jnp.float32)
        dlogits = coef[:, None] * (p * zcoef[:, None] - hit)  # (T, blk)
        dh = dh + (dlogits @ wblk.astype(jnp.float32).T)
        dwblk = h2.astype(jnp.float32).T @ dlogits            # (d, blk)
        return dh, dwblk

    dh, dwb = jax.lax.scan(step, jnp.zeros((T, d), jnp.float32),
                           (wb, jnp.arange(nb)))
    dw = dwb.transpose(1, 0, 2).reshape(d, Vp)[:, :V]
    d_labels = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    d_mask = None if mask is None else jnp.zeros_like(mask)
    return (dh.reshape(B, S, d).astype(h.dtype), dw.astype(w.dtype),
            d_labels, d_mask)


blockwise_xent.defvjp(_fwd, _bwd)


def _token_blocks(h, labels, mask, block):
    """Token-major blocks of ``h`` (B,S,d), ``labels`` and the loss mask
    (ones where ``mask`` is None), the last padded with masked-out rows:
    ``(hb (nb,block,d), lb (nb,block), mb (nb,block))``."""
    B, S, d = h.shape
    T = B * S
    h2 = h.reshape(T, d)
    lab = labels.reshape(T)
    m1 = (mask.reshape(T).astype(jnp.float32) if mask is not None
          else jnp.ones((T,), jnp.float32))
    block = min(block, T)
    pad = (-T) % block
    if pad:
        h2 = jnp.pad(h2, ((0, pad), (0, 0)))
        lab = jnp.pad(lab, (0, pad))
        m1 = jnp.pad(m1, (0, pad))
    nb = h2.shape[0] // block
    return (h2.reshape(nb, block, d), lab.reshape(nb, block),
            m1.reshape(nb, block))


def _block_xent(hblk, w, lblk, z_loss):
    """One token block against the whole vocabulary: its f32 logits, their
    logsumexp and the per-token loss."""
    lo = hblk @ w.astype(hblk.dtype)
    logits = lo.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # the gold logit is gathered before the (exact) cast: gathering from the
    # f32 copy makes XLA write that whole (block, V) f32 array to HBM
    gold = jnp.take_along_axis(lo, lblk[:, None], axis=-1)[:, 0].astype(
        jnp.float32)
    per = lse - gold
    if z_loss:
        per = per + z_loss * lse ** 2
    return logits, lse, per


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def token_chunked_xent(h: jax.Array, w: jax.Array, labels: jax.Array,
                       mask=None, block: int = 4096, z_loss: float = 0.0
                       ) -> jax.Array:
    """Token-block-chunked xent: a scan over token blocks, so only
    O(block × V) logits are ever live.  Unlike the vocab-chunked variant
    this keeps the vocab dim contiguous, so under GSPMD the per-block matmul
    stays TP-sharded on the model axis (vocab-chunking would serialize TP).

    Undifferentiated, each block costs one logits matmul.  Differentiated
    (custom VJP), the forward scan also emits ``dh`` and accumulates ``dW``
    from the same logits, in ``h``'s and ``w``'s dtypes; the backward scales
    them by the cotangent and never recomputes a logits block."""
    hb, lb, mb = _token_blocks(h, labels, mask, block)

    def body(carry, inp):
        lsum, msum = carry
        hblk, lblk, mblk = inp
        _, _, per = _block_xent(hblk, w, lblk, z_loss)
        return (lsum + jnp.sum(per * mblk), msum + jnp.sum(mblk)), None

    (lsum, msum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hb, lb, mb))
    return lsum / jnp.maximum(msum, 1.0)


def _token_chunked_fwd(h, w, labels, mask, block, z_loss):
    hb, lb, mb = _token_blocks(h, labels, mask, block)
    denom = jnp.maximum(jnp.sum(mb), 1.0)
    V = w.shape[1]

    def body(carry, inp):
        lsum, dw = carry
        hblk, lblk, mblk = inp
        logits, lse, per = _block_xent(hblk, w, lblk, z_loss)
        p = jnp.exp(logits - lse[:, None])
        if z_loss:
            p = p * (1.0 + 2.0 * z_loss * lse)[:, None]
        hit = lblk[:, None] == jnp.arange(V)[None, :]
        dlogits = ((mblk / denom)[:, None]
                   * (p - hit.astype(jnp.float32))).astype(hblk.dtype)
        dh_blk = dlogits @ w.astype(hblk.dtype).T
        dw = dw + (hblk.T @ dlogits).astype(w.dtype)
        return (lsum + jnp.sum(per * mblk), dw), dh_blk

    (lsum, dw), dhb = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros_like(w)), (hb, lb, mb))
    B, S, d = h.shape
    dh = dhb.reshape(-1, d)[:B * S].reshape(B, S, d)
    return lsum / denom, (dh, dw)


def _token_chunked_bwd(block, z_loss, res, g):
    dh, dw = res
    # None: zero cotangents for labels (float0) and mask, with no residual
    return ((dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), None, None)


token_chunked_xent.defvjp(_token_chunked_fwd, _token_chunked_bwd)
