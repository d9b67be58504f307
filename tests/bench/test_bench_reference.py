"""The float32 reference against the program's model at small widths on
the CPU: the same initial weights bit for bit, the same loss and gradient
when the program computes in float32, and a comparison that a bfloat16 or
float8 computation fails."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.reference import compare  # noqa: E402
from bench.reference.data import batch_at  # noqa: E402
from bench.reference.dense import (Reference, Trainer, _fp8,  # noqa: E402
                                   follow)

from repro.models.lm import ModelConfig, StagedLM  # noqa: E402

OPT = {"lr": 3e-4, "warmup": 0, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0}
# float32 against float32 at highest precision: the two orders of summation
# part by a few float32 roundings (unit roundoff 6e-8) per product.
F32_LOSS, F32_GRAD = 1e-5, 1e-5


def tiny(mlp="swiglu", dt="float32"):
    return {"name": "tiny", "num_layers": 2, "n_chunks": 2, "d_model": 128,
            "n_heads": 4, "n_kv_heads": 2, "d_ff": 256, "vocab_size": 512,
            "qkv_bias": True, "mlp_kind": mlp, "rope_theta": 10000.0,
            "dtype": dt, "param_dtype": dt, "scan_layer_remat": "none",
            "logits_chunk": 0}


def program(m):
    kw = dict(m, dtype=getattr(jnp, m["dtype"]),
              param_dtype=getattr(jnp, m["param_dtype"]))
    return StagedLM(ModelConfig(**kw))


def program_vs_reference(m, key):
    model = program(m)
    params = model.init(key)
    batch = batch_at(m["vocab_size"], 4, 48, 7, 0)
    lp, gp = jax.value_and_grad(model.loss_fn)(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    # small blocks, so that the query-block and token-block paths run
    tr = Trainer(Reference(m, q_block=16, tok_block=32), OPT)
    rp = jax.tree.map(lambda x: x.astype(jnp.float32),
                      jax.jit(Reference(m).init)(key))
    lr, gr = tr.loss_and_grad(rp, batch)
    return abs(float(lp) - lr), compare.leaf_gap(tr.leaf_norms(gp),
                                                 tr.leaf_norms(gr))[0]


@pytest.mark.parametrize("mlp", ["swiglu", "gelu"])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_initial_weights_are_the_programs(mlp, dt):
    m = tiny(mlp, dt)
    key = jax.random.PRNGKey(11)
    got = jax.jit(Reference(m).init)(key)
    want = program(m).init(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))


@pytest.mark.parametrize("mlp", ["swiglu", "gelu"])
def test_float32_program_matches_reference(mlp):
    loss, grad = program_vs_reference(tiny(mlp), jax.random.PRNGKey(3))
    assert loss < F32_LOSS and grad < F32_GRAD


@pytest.mark.parametrize("mlp", ["swiglu", "gelu"])
def test_bfloat16_program_fails_the_float32_comparison(mlp):
    # bf16 keeps 8 significant bits: the loss and the gradient norms part
    # from the reference by more than float32 rounding allows
    loss, grad = program_vs_reference(tiny(mlp, "bfloat16"),
                                      jax.random.PRNGKey(3))
    assert loss > F32_LOSS and grad > 100 * F32_GRAD


def test_float8_control_fails_the_cell_limits():
    """The control (every product in float8) against the float32 reference
    over the checked steps, at a size a test can hold: it fails the
    training cell's limits."""
    m = tiny("swiglu", "bfloat16")
    key = jax.random.PRNGKey(5)
    bs = [batch_at(512, 4, 48, 9, k) for k in range(3)]
    lrs = [3e-4] * 3
    ref = follow(Reference(m), OPT, key, bs, lrs)
    ctl = follow(Reference(m, quant="fp8"), OPT, key, bs, lrs)
    nums = compare.train_numbers(ctl, ref)
    limits = json.loads((ROOT / "bench" / "limits"
                         / "qwen1.5-4b.train.fullmem.json").read_text())
    assert any(nums[k] > limits[k] for k in ("loss", "grad", "update")), (
        nums, limits)


def test_leaf_gap_and_moved_leaves():
    ref = np.array([1.0, 2.0, 3.0, 1e-9])
    prog = np.array([1.0, 2.2, 3.0, 1e-3])
    gap, i = compare.leaf_gap(prog, ref)
    # the tiny leaf is measured against the median (2.0): 1e-3 / 2.0
    assert i == 1 and gap == pytest.approx(0.1)
    keep = compare.moved_leaves(ref)
    assert list(keep) == [True, True, True, False]


def test_reference_update_is_stored_in_the_parameter_dtype():
    """An AdamW step of the bfloat16 reference leaves every parameter on a
    bfloat16 value: a norm scale of 1 does not move by lr, a bias from 0
    does, and each leaf equals its own round trip through bfloat16."""
    m = tiny("swiglu", "bfloat16")
    ref = Reference(m)
    tr = Trainer(ref, OPT)
    p0 = jax.jit(ref.init)(jax.random.PRNGKey(2))
    params = jax.tree.map(lambda x: x.astype(jnp.float32), p0)
    _, grads = tr.loss_and_grad(params, batch_at(512, 2, 32, 4, 0))
    moments = [(np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
               for x in jax.tree.leaves(p0)]
    new = tr.apply(params, grads, moments, 0, 3e-4)
    for x in jax.tree.leaves(new):
        x = np.asarray(x)
        assert np.array_equal(x, x.astype(jnp.bfloat16).astype(np.float32))
    assert np.all(np.asarray(new["final_norm"]["scale"]) == 1.0)
    assert np.any(np.asarray(new["chunks"][0]["attn"]["wq"]["bias"]) != 0.0)


def test_float8_rounding_keeps_three_mantissa_bits():
    """The control's rounding: scaled to 240 at the largest magnitude,
    every normal value on the float8 e4m3 grid (4 significant bits)."""
    x = jnp.asarray(np.random.default_rng(1).standard_normal(4096), jnp.float32)
    y = np.asarray(jax.jit(_fp8)(x))
    s = float(np.max(np.abs(np.asarray(x)))) / 240.0
    q = y / s
    normal = np.abs(q) >= 2.0 ** -6
    mant, _ = np.frexp(q[normal])
    assert np.allclose(mant * 16, np.round(mant * 16), atol=1e-4)
    assert np.max(np.abs(q)) == pytest.approx(240.0)
    assert np.max(np.abs(y - np.asarray(x))[normal]
                  / np.abs(np.asarray(x))[normal]) <= 2.0 ** -4 + 1e-6
