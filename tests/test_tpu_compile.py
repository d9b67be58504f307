"""Compile rehearsals for a TPU v5e that is described, not attached: the
main-path Pallas kernels at real widths, each compiled for one chip of a
``v5e:2x2`` topology.  Nothing runs; a pass says the chip's compiler accepts
the kernel (tiling, VMEM, lowering), not that it is fast or correct.

The topology is described inside a module-scoped fixture (never at import),
so that under several test workers only the worker given this file loads
the TPU compiler.  The persistent compile cache is off around these
compiles: entries written for a described chip cannot be read back here.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.pallas.mosaic.error_handling import MosaicError
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in HLO"
    return compiled


def test_flash_attention_fwd_bwd_qwen_widths(one_chip):
    from repro.kernels.flash_attention import ops

    # qwen1.5-4b: 20 heads of 128, causal, at a 2048-token training sequence
    q = jax.ShapeDtypeStruct((1, 2048, 20, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss_and_grads(q, k, v):
        def f(q, k, v):
            return ops.flash_attention(q, k, v, True).astype(jnp.float32).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    _compile(loss_and_grads, q, q, q)


def test_rmsnorm_qwen_width(one_chip):
    from repro.kernels.rmsnorm import ops

    x = jax.ShapeDtypeStruct((8192, 2560), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2560,), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x, s: ops.rms_norm(x, s), x, s)


def test_ssd_mamba2_widths(one_chip):
    from repro.kernels.ssd import ops

    # mamba2-1.3b: d_inner 4096 = 64 heads of 64, state 128, one group,
    # chunk 256, over a 2048-token sequence
    B, S, H, P, G, N = 1, 2048, 64, 64, 1, 128

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _compile(lambda x, dt, A, Bm, Cm: ops.ssd_chunked(x, dt, A, Bm, Cm, 256),
             spec((B, S, H, P), jnp.bfloat16), spec((B, S, H), jnp.float32),
             spec((H,), jnp.float32), spec((B, S, G, N), jnp.bfloat16),
             spec((B, S, G, N), jnp.bfloat16))


def _fused_operand_specs(one_chip, L=12, S=500):
    """Shapes of the fused fill's operands for an L-stage chain at S slots
    (pruning off: the table is S + 1 = 501 lanes wide)."""
    from repro.core import dp_kernels
    from repro.core.chain import Chain
    from repro.kernels.dp_fill import kernel, ops

    rng = np.random.default_rng(0)
    ch = Chain.make(*(rng.integers(1, 40, L + 1).astype(float)
                      for _ in range(4)))
    dchain = ch.discretize(float(S), S)
    ctx = dp_kernels._FillCtx(dp_kernels._views(dchain), L, S)
    br = min(kernel.DEFAULT_BLOCK_ROWS, L)
    fo = ops._FusedOperands(ctx, None, br)
    arrays = (fo.initial_table(dp_kernels.BandedTable(L, S)), fo.off, fo.wa,
              fo.wb, fo.cum, fo.uf, fo.ub, fo.mn, fo.ma)
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
             for a in arrays]
    vec = jax.ShapeDtypeStruct((fo.vec,), np.float32, sharding=one_chip)
    return dict(L=L, W=fo.W, block_rows=br), specs, vec


_FUSED_REFUSED = (
    "Mosaic refuses the fused fill: 'cannot statically prove that index in "
    "dimension 0 is a multiple of 128' on a vector.load from memref<128xi32> "
    "(the off_ref scalar reads); its dynamic lane slices and in-kernel "
    "take_along_axis gather need a TPU rewrite of the kernel")


@pytest.mark.xfail(strict=True, raises=MosaicError, reason=_FUSED_REFUSED)
def test_dp_fill_fused_two_tier(one_chip):
    from repro.kernels.dp_fill import kernel

    static, specs, _ = _fused_operand_specs(one_chip)
    assert static["W"] == 501
    _compile(functools.partial(kernel.fused_fill_two_tier, allow_fall=True,
                               **static), *specs)


@pytest.mark.xfail(strict=True, raises=MosaicError, reason=_FUSED_REFUSED)
def test_dp_fill_fused_offload(one_chip):
    from repro.kernels.dp_fill import kernel

    static, specs, vec = _fused_operand_specs(one_chip)
    _compile(functools.partial(kernel.fused_fill_offload, allow_fall=True,
                               host_on=True, **static),
             specs[0], *specs, vec, vec)
