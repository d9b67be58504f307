"""Parameter estimation (paper §5.1) — produce a ``Chain`` cost model for a
sequence of JAX stage functions.

Two modes, mirroring the two ways we run:

- **analytic** (dry-run / TPU-target): per-stage FLOPs from
  ``jit(fn).lower(...).compile().cost_analysis()`` divided by the planned
  device's peak FLOP/s (:mod:`repro.core.devices`); activation/residual
  *sizes* are exact, from ``jax.eval_shape`` of the stage and of its VJP
  (the VJP closure is a pytree whose leaves are the residual tensors —
  JAX's ``ā^l``).  Residual leaves that are shape/dtype-
  identical to parameter leaves are greedily excluded (the paper removes
  model/grad memory from the activation budget, §3.1).
- **measured** (CPU reproduction benchmarks): wall-clock each stage's forward
  and forward+backward, exactly like the paper's measurement tool.

Both return a :class:`repro.core.chain.Chain` (sizes in bytes, times in
seconds for measured / FLOP-derived seconds for analytic).
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .chain import Chain, HostTransferModel
from .devices import device_peaks


def measure_host_bandwidth(sample_bytes: int = 1 << 26, repeats: int = 3,
                           latency: float = 1e-4) -> HostTransferModel:
    """Measure the effective device↔host copy bandwidth (paper-§5.1 style:
    wall-clock the actual operation).  Device→host is a forced ``np.asarray``
    materialization, host→device a ``jax.device_put`` — both are real copies
    on every backend, including CPU (where they time memcpy, the honest cost
    of the 'host tier' there)."""
    n = max(sample_bytes // 4, 1)
    dev = jnp.ones((n,), jnp.float32)
    jax.block_until_ready(dev)
    t0 = time.perf_counter()
    for _ in range(repeats):
        host = np.array(dev, copy=True)  # asarray may alias on CPU backends
    t_d2h = (time.perf_counter() - t0) / repeats
    t0 = time.perf_counter()
    for _ in range(repeats):
        back = jax.device_put(host)
        jax.block_until_ready(back)
    t_h2d = (time.perf_counter() - t0) / repeats
    nbytes = n * 4
    return HostTransferModel(
        bandwidth_d2h=nbytes / max(t_d2h, 1e-12),
        bandwidth_h2d=nbytes / max(t_h2d, 1e-12),
        latency=latency)


def _bytes_of(spec) -> int:
    return int(np.prod(spec.shape)) * np.dtype(spec.dtype).itemsize if spec.shape else np.dtype(spec.dtype).itemsize


def _pytree_bytes(tree) -> int:
    return sum(_bytes_of(l) for l in jax.tree.leaves(tree))


def residual_bytes(fn: Callable, p: Any, a: Any) -> int:
    """ω_ā for one stage: VJP-residual bytes minus param-aliased leaves."""
    _, vjp_spec = jax.eval_shape(lambda p_, a_: jax.vjp(fn, p_, a_), p, a)
    res = jax.tree.leaves(vjp_spec)
    param_shapes = collections.Counter(
        (tuple(l.shape), jnp.dtype(l.dtype).name) for l in jax.tree.leaves(
            jax.eval_shape(lambda q: q, p)))
    total = 0
    for leaf in res:
        key = (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)
        if param_shapes[key] > 0:
            param_shapes[key] -= 1  # assume it aliases a live param buffer
            continue
        total += _bytes_of(leaf)
    return total


def _flops_of(fn: Callable, *args) -> float:
    ca = jax.jit(fn).lower(*args).compile().cost_analysis() or {}
    return float(ca.get("flops", 0.0))


def profile_stages_analytic(
    stages: Sequence[Callable],
    params: Sequence[Any],
    x: Any,
    peak_flops: Optional[float] = None,
    activation_shard_factor: float = 1.0,
    flops_fwd: Optional[Sequence[float]] = None,
    flops_bwd: Optional[Sequence[float]] = None,
    host: Optional[HostTransferModel] = None,
) -> Chain:
    """Build the chain cost model without executing anything.

    ``activation_shard_factor`` divides all activation/residual sizes — pass
    the product of mesh-axis sizes over which activations are sharded so the
    DP sees *per-device* bytes.  ``flops_fwd/bwd`` skip the per-stage compiles
    when the caller already knows the FLOP counts (e.g. from config math).
    ``peak_flops`` defaults to the bf16 peak of the device JAX runs on
    (:mod:`repro.core.devices`).
    """
    if peak_flops is None:
        peak_flops = device_peaks(jax.devices()[0]).flops_bf16
    n = len(stages)
    uf, ub, wa, wabar = [], [], [], []
    wa.append(_pytree_bytes(jax.eval_shape(lambda v: v, x)) / activation_shard_factor)
    a = x
    for i, (fn, p) in enumerate(zip(stages, params)):
        out_spec = jax.eval_shape(fn, p, a)
        if flops_fwd is not None:
            f_fwd = flops_fwd[i]
        else:
            f_fwd = _flops_of(fn, p, a)
        if flops_bwd is not None:
            f_bwd = flops_bwd[i]
        else:
            def fwd_bwd(p_, a_, ct):
                out, vjp = jax.vjp(fn, p_, a_)
                return vjp(ct)
            ct = jax.eval_shape(fn, p, a)
            ct = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), ct)
            f_bwd = max(_flops_of(fwd_bwd, p, a, ct) - f_fwd, f_fwd)
        uf.append(f_fwd / peak_flops)
        ub.append(f_bwd / peak_flops)
        wabar.append(residual_bytes(fn, p, a) / activation_shard_factor)
        if i < n - 1:
            wa.append(_pytree_bytes(out_spec) / activation_shard_factor)
        a = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), out_spec) \
            if flops_fwd is None else out_spec
    return Chain.make(uf=uf, ub=ub, wa=wa, wabar=wabar, host=host)


def profile_stages_measured(
    stages: Sequence[Callable],
    params: Sequence[Any],
    x: Any,
    repeats: int = 3,
    host: Optional[HostTransferModel] = None,
) -> Chain:
    """Wall-clock per-stage costs (the paper's §5.1 measurement phase)."""
    n = len(stages)
    uf, ub, wa, wabar = [], [], [], []
    wa.append(_pytree_bytes(jax.eval_shape(lambda v: v, x)))
    a = x
    for i, (fn, p) in enumerate(zip(stages, params)):
        jfn = jax.jit(fn)

        def fwd_bwd(p_, a_, ct):
            out, vjp = jax.vjp(fn, p_, a_)
            return vjp(ct)

        jfb = jax.jit(fwd_bwd)
        out = jfn(p, a)
        ct = jax.tree.map(jnp.ones_like, out)
        jax.block_until_ready(jfb(p, a, ct))  # warmup both

        t0 = time.perf_counter()
        for _ in range(repeats):
            out = jfn(p, a)
        jax.block_until_ready(out)
        t_fwd = (time.perf_counter() - t0) / repeats

        t0 = time.perf_counter()
        for _ in range(repeats):
            g = jfb(p, a, ct)
        jax.block_until_ready(g)
        t_fb = (time.perf_counter() - t0) / repeats

        uf.append(t_fwd)
        ub.append(max(t_fb - t_fwd, 0.25 * t_fwd))
        wabar.append(residual_bytes(fn, p, a))
        if i < n - 1:
            wa.append(_pytree_bytes(jax.eval_shape(lambda v: v, out)))
        a = out
    return Chain.make(uf=uf, ub=ub, wa=wa, wabar=wabar, host=host)
