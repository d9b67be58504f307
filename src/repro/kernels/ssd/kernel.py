"""Mamba2 SSD intra-chunk computation — Pallas TPU kernel.

This is the TPU adaptation of the SSD "block decomposition" (arXiv:2405.21060
§6): for each (batch, head, chunk) the kernel computes, entirely in VMEM,

- the *diagonal* (within-chunk) output block
    ``y = ((C·Bᵀ) ⊙ L ⊙ dt) · x``       — two (Q×Q)/(Q×P) MXU matmuls,
- the chunk's *state contribution*
    ``S_c = (B ⊙ decay ⊙ dt)ᵀ · x``      — one (N×Q)·(Q×P) MXU matmul,

leaving only the tiny inter-chunk scan over S/Q chunk states to XLA (a
sequential O(S/Q) recurrence with (H,P,N)-sized state, negligible FLOPs).
The CUDA version streams warps over the sequence; on TPU the same math maps
onto the 128×128 systolic array with Q=chunk as the contracting tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref):
    # blocks: x (1,1,Q,P), dt (1,1,1,Q) [a lane row], b/c (1,1,Q,N)
    # [heads pre-broadcast to groups], outputs y (1,1,Q,P), s (1,1,P,N);
    # a is the whole (BH,) vector in SMEM, read as this head's scalar
    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, Q)
    a = a_ref[pl.program_id(0)]                  # scalar
    bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    da = dt * a                                  # (1, Q)
    Q = x.shape[0]
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # within-chunk cumsum as a matmul with the upper-triangular ones (Mosaic
    # has no cumsum); HIGHEST keeps the operands in f32, not bf16
    cs = jax.lax.dot_general(da, (li <= lj).astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (1, Q)
    # L[i, j] = exp(cs_i - cs_j) for j <= i else 0
    L = jnp.where(lj <= li, jnp.exp(jnp.transpose(cs) - cs), 0.0)

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    w = scores * L * dt
    y_ref[0, 0] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    decay = jnp.exp(cs[:, Q - 1:] - cs)          # (1, Q)
    bw = bm * jnp.transpose(decay * dt)          # (Q, N)
    s_ref[0, 0] = jax.lax.dot_general(
        x, bw, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)  # (P, N)


def ssd_chunk_blocks(x: jax.Array, dt: jax.Array, A: jax.Array,
                     Bm: jax.Array, Cm: jax.Array,
                     interpret: bool = False):
    """Intra-chunk terms.  Shapes (already chunked by ops.py):
    x: (BH, nc, Q, P), dt: (BH, nc, Q), A: (BH,), Bm/Cm: (BH, nc, Q, N) —
    heads pre-broadcast to groups.  Returns (y_diag, states):
    y_diag (BH, nc, Q, P) f32, states (BH, nc, P, N) f32."""
    BH, nc, Q, P = x.shape
    N = Bm.shape[-1]
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt.reshape(BH, nc, 1, Q), A.astype(jnp.float32), Bm, Cm)
