"""Per-kernel correctness: shape/dtype sweeps vs the pure-jnp oracles, in
Pallas interpret mode (kernel bodies execute in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fops, ref as fref
from repro.kernels.rmsnorm import ops as rops, ref as rref
from repro.kernels.ssd import ops as sops, ref as sref
from repro.kernels.xent import ops as xops, ref as xref


@pytest.fixture(autouse=True)
def interpret_mode():
    fops.set_interpret(True)
    rops.set_interpret(True)
    sops.set_interpret(True)
    yield
    fops.set_interpret(False)
    rops.set_interpret(False)
    sops.set_interpret(False)


# -- flash attention ---------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, K, D, dtype)
    (2, 128, 4, 4, 64, jnp.float32),
    (1, 256, 4, 2, 128, jnp.float32),
    (2, 96, 6, 2, 32, jnp.float32),     # S not a block multiple, D < 128
    (1, 130, 8, 1, 128, jnp.float32),   # MQA, ragged S
    (2, 128, 4, 4, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,K,D,dtype", FLASH_CASES)
def test_flash_attention_fwd(B, S, H, K, D, dtype):
    key = jax.random.PRNGKey(S * H + D)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, K, D), dtype)
    v = jax.random.normal(ks[2], (B, S, K, D), dtype)
    out = fops.flash_attention(q, k, v, True)
    exp = fref.attention(q, k, v, True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol, atol=tol)


def test_flash_attention_grads():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    g1 = jax.grad(lambda q_, k_, v_: fops.flash_attention(q_, k_, v_, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q_, k_, v_: fref.attention(q_, k_, v_, True).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


# -- rmsnorm ------------------------------------------------------------------

@pytest.mark.parametrize("shape,dtype", [
    ((4, 37, 256), jnp.float32),
    ((3, 128), jnp.float32),
    ((2, 16, 512), jnp.bfloat16),
])
def test_rmsnorm(shape, dtype):
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, shape, dtype)
    s = (jax.random.normal(jax.random.fold_in(key, 1), shape[-1:]) * 0.1
         + 1).astype(dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(rops.rms_norm(x, s), np.float32),
                               np.asarray(rref.rms_norm(x, s), np.float32),
                               rtol=tol, atol=tol)


def test_rmsnorm_grads():
    """Backward parity for the custom-VJP wrapper (fwd = Pallas kernel in
    interpret mode, bwd = recompute-from-inputs): kernel changes that skew
    the saved residuals or the recompute surface here, on CPU CI."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (3, 17, 128))
    s = jax.random.normal(jax.random.fold_in(key, 1), (128,)) * 0.1 + 1

    def f_k(x_, s_):
        return (rops.rms_norm(x_, s_) ** 2).sum()

    def f_r(x_, s_):
        return (rref.rms_norm(x_, s_) ** 2).sum()

    g1 = jax.grad(f_k, argnums=(0, 1))(x, s)
    g2 = jax.grad(f_r, argnums=(0, 1))(x, s)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_flash_attention_non_causal():
    key = jax.random.PRNGKey(21)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 2, 64))
    v = jax.random.normal(ks[2], (1, 128, 2, 64))
    out = fops.flash_attention(q, k, v, False)
    exp = fref.attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-5, atol=2e-5)


# -- SSD ----------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, P, G, N, Q)
    (2, 64, 4, 16, 1, 32, 16),
    (1, 48, 2, 8, 2, 16, 16),   # grouped B/C, S not multiple of Q? 48/16=3 ok
    (1, 40, 2, 8, 1, 16, 16),   # ragged chunks (padding path)
]


@pytest.mark.parametrize("B,S,H,P,G,N,Q", SSD_CASES)
def test_ssd_kernel_vs_naive(B, S, H, P, G, N, Q):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_naive, st_naive = sref.ssd_naive(x, dt, A, Bm, Cm)
    y_ref, st_ref = sref.ssd_chunked(x, dt, A, Bm, Cm, Q)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_naive),
                               rtol=2e-4, atol=2e-4)
    y_k, st_k = sops.ssd_chunked(x, dt, A, Bm, Cm, Q)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_kernel_grads():
    B, S, H, P, G, N, Q = 1, 32, 2, 8, 1, 16, 8
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3

    def f_k(x_):
        return sops.ssd_chunked(x_, dt, A, Bm, Cm, Q)[0].sum()

    def f_r(x_):
        return sref.ssd_chunked(x_, dt, A, Bm, Cm, Q)[0].sum()

    np.testing.assert_allclose(np.asarray(jax.grad(f_k)(x)),
                               np.asarray(jax.grad(f_r)(x)),
                               rtol=1e-4, atol=1e-4)


def test_ssd_state_continuation():
    """Chunked SSD over [0:S] == two calls over [0:S/2], [S/2:S] with the
    carried state — the property decode streaming relies on."""
    B, S, H, P, G, N, Q = 1, 64, 2, 8, 1, 16, 16
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_full, st_full = sref.ssd_chunked(x, dt, A, Bm, Cm, Q)
    h = S // 2
    y1, st1 = sref.ssd_chunked(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], Q)
    y2, st2 = sref.ssd_chunked(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:],
                               Q, init_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=2e-4, atol=2e-4)


# -- cross-entropy -------------------------------------------------------------

@pytest.mark.parametrize("V,block", [(1000, 128), (777, 256), (64, 128)])
def test_vocab_blockwise_xent(V, block):
    B, S, d = 2, 8, 32
    key = jax.random.PRNGKey(11)
    h = jax.random.normal(key, (B, S, d))
    w = jax.random.normal(jax.random.fold_in(key, 1), (d, V)) * 0.1
    labels = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0, V)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) > 0.3
            ).astype(jnp.float32)
    l1 = xops.blockwise_xent(h, w, labels, mask, block=block)
    l2 = xref.xent_from_hidden(h, w, labels, mask)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g1 = jax.grad(lambda *a: xops.blockwise_xent(*a, block), argnums=(0, 1))(
        h, w, labels, mask)
    g2 = jax.grad(lambda *a: xref.xent_from_hidden(*a), argnums=(0, 1))(
        h, w, labels, mask)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def _xent_case(dtype=jnp.float32, masked=True):
    B, S, d, V = 2, 10, 16, 301
    key = jax.random.PRNGKey(13)
    h = jax.random.normal(key, (B, S, d)).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(key, 1), (d, V)) * 0.1
         ).astype(dtype)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (B, S), 0, V)
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (B, S)) > 0.2
            ).astype(jnp.float32) if masked else None
    return h, w, labels, mask


# (block, mask, z_loss, dtype); T = 20 tokens, so blocks 7 and 16 pad the
# last block, and 64 is cut to one block of 20
@pytest.mark.parametrize("block, masked, z_loss, dtype", [
    (4, True, 0.0, jnp.float32),
    (16, True, 0.0, jnp.float32),
    (64, True, 0.0, jnp.float32),
    (4, False, 0.0, jnp.float32),
    (4, True, 1e-3, jnp.float32),
    (7, True, 0.0, jnp.float32),
    (7, True, 1e-3, jnp.bfloat16),
], ids=["4", "16", "64", "no_mask", "z_loss", "padded", "bf16"])
def test_token_chunked_xent(block, masked, z_loss, dtype):
    """Loss and both gradients against the full-logits oracle.  The custom
    VJP makes (dh, dW) in the forward scan; in bf16 they are rounded to
    bf16 per block, so they may sit a few bf16 roundings of the largest
    entry away from the oracle's."""
    h, w, labels, mask = _xent_case(dtype, masked)
    l1 = xops.token_chunked_xent(h, w, labels, mask, block=block,
                                 z_loss=z_loss)
    l2 = xref.xent_from_hidden(h, w, labels, mask, z_loss)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g1 = jax.grad(lambda h_, w_: xops.token_chunked_xent(
        h_, w_, labels, mask, block, z_loss), argnums=(0, 1))(h, w)
    g2 = jax.grad(lambda h_, w_: xref.xent_from_hidden(
        h_, w_, labels, mask, z_loss), argnums=(0, 1))(h, w)
    for a, b in zip(g1, g2):
        assert a.dtype == b.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        else:
            np.testing.assert_allclose(
                a, b, rtol=0, atol=4 * 2.0 ** -8 * np.abs(b).max())


def _dots(fn, *args):
    from repro.launch.roofline import dot_shapes_from_hlo

    return dot_shapes_from_hlo(jax.jit(fn).lower(*args).compile().as_text())


@pytest.mark.parametrize("masked", [True, False], ids=["mask", "no_mask"])
def test_token_chunked_xent_makes_each_logits_block_once(masked):
    """Differentiated, the compiled scan holds three matmuls (logits, dh,
    dW) and no replay of the logits; undifferentiated, one."""
    h, w, labels, mask = _xent_case(masked=masked)

    def loss(h_, w_):
        return xops.token_chunked_xent(h_, w_, labels, mask, 8)

    assert len(_dots(jax.value_and_grad(loss, argnums=(0, 1)), h, w)) == 3
    assert len(_dots(loss, h, w)) == 1


def test_staged_lm_head_is_never_replayed_under_full_remat():
    """A tiny ``StagedLM`` with a token-chunked head: under
    ``full_remat_tree`` its gradients are store-all's, and the compiled
    gradient holds two matmuls with a vocabulary-sized result (the logits
    and dW), as the head is never inside a checkpoint."""
    from repro.core.rematerialize import full_remat_tree
    from repro.models.lm import ModelConfig, StagedLM

    cfg = ModelConfig(name="tiny", num_layers=2, n_chunks=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=384,
                      qkv_bias=True, mlp_kind="swiglu",
                      scan_layer_remat="full", logits_chunk=16)
    model = StagedLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    B, S = 2, 24
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.fold_in(key, 1), (B, S),
                                          0, cfg.vocab_size),
             "loss_mask": jnp.ones((B, S), jnp.float32)}
    tree = full_remat_tree(model.n_stages() - 1)
    g_store = jax.jit(jax.grad(lambda p: model.loss_fn(p, batch)))(params)
    remat = jax.grad(lambda p: model.loss_fn(p, batch, tree=tree))
    g_remat = jax.jit(remat)(params)
    for a, b in zip(jax.tree.leaves(g_remat), jax.tree.leaves(g_store)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
    vocab = [s for s in _dots(remat, params) if cfg.vocab_size in s]
    assert len(vocab) == 2, vocab
