"""AdamW in pure JAX (pytree-generic), with global-norm clipping.

Moments are kept in f32 regardless of param dtype (bf16 params train
stably with f32 first/second moments); under the production mesh the moment
trees inherit the params' (FSDP × TP) sharding, i.e. ZeRO-3-style placement
comes from GSPMD for free.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: Optional[float] = 1.0


def adamw_init(params: Any) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree.map(zeros, params),
        "nu": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Any) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


@jax.named_scope("optimizer.adamw")
def adamw_update(cfg: AdamWConfig, grads: Any, state: dict, params: Any,
                 lr: Optional[jax.Array] = None) -> Tuple[Any, dict, dict]:
    """Returns (new_params, new_state, metrics); traced under the
    ``optimizer.adamw`` scope, which names its operations on the device."""
    gnorm = global_norm(grads)
    if cfg.clip_norm is not None:
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)
    count = state["count"] + 1
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - b2 ** count.astype(jnp.float32)
    lr = cfg.lr if lr is None else lr

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m2 = b1 * m + (1 - b1) * gf
        v2 = b2 * v + (1 - b2) * gf * gf
        step = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + cfg.eps)
        if cfg.weight_decay and p.ndim >= 2:  # decay matrices, not norms/bias
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(state["mu"])
    flat_v = jax.tree.leaves(state["nu"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm,
               "param_norm": global_norm(params)}
    return new_p, {"mu": new_m, "nu": new_v, "count": count}, metrics
