"""Feed-forward variants: GELU MLP, SwiGLU, and capacity-based top-k MoE
(shared + routed experts, DeepSeek-V2/Moonlight style).

The MoE uses Mesh-TensorFlow-style dispatch/combine einsums so that under
GSPMD the expert dimension shards on the ``model`` axis and routing lowers to
all-to-alls — no per-token gather/scatter host logic.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from .common import dense_apply, dense_init

Params = Dict[str, Any]


# -- dense MLPs ---------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype, kind: str = "swiglu",
             num_layers: int = 1) -> Params:
    ks = jax.random.split(key, 3)
    out_scale = 1.0 / math.sqrt(d_ff * max(num_layers, 1))
    if kind in ("swiglu", "geglu"):
        return {
            "wi_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "wi_up": dense_init(ks[1], d_model, d_ff, dtype),
            "wo": dense_init(ks[2], d_ff, d_model, dtype, scale=out_scale),
        }
    return {  # plain gelu MLP (StarCoder2, MusicGen)
        "wi": dense_init(ks[0], d_model, d_ff, dtype),
        "wo": dense_init(ks[1], d_ff, d_model, dtype, scale=out_scale),
    }


def mlp_param_axes(kind: str = "swiglu") -> Params:
    if kind in ("swiglu", "geglu"):
        return {"wi_gate": {"kernel": ("embed", "mlp")},
                "wi_up": {"kernel": ("embed", "mlp")},
                "wo": {"kernel": ("mlp", "embed")}}
    return {"wi": {"kernel": ("embed", "mlp")},
            "wo": {"kernel": ("mlp", "embed")}}


def mlp_apply(p: Params, x: jax.Array, kind: str = "swiglu") -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(dense_apply(p["wi_gate"], x)) * dense_apply(p["wi_up"], x)
    elif kind == "geglu":
        h = jax.nn.gelu(dense_apply(p["wi_gate"], x), approximate=True) \
            * dense_apply(p["wi_up"], x)
    else:
        h = jax.nn.gelu(dense_apply(p["wi"], x), approximate=True)
    h = constrain(h, "act_batch", "act_seq", "act_mlp")
    y = dense_apply(p["wo"], h)
    return constrain(y, "act_batch", "act_seq", "act_embed")


# -- mixture of experts --------------------------------------------------------

def moe_init(key, cfg, dtype) -> Params:
    d, e_ff = cfg.d_model, cfg.moe_d_ff
    E = cfg.num_experts
    ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(e_ff * max(cfg.num_layers, 1))
    p: Params = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        # stacked experts: (E, d, e_ff) / (E, e_ff, d)
        "we_gate": {"kernel": _stack_init(ks[1], E, (d, e_ff), dtype, scale_in)},
        "we_up": {"kernel": _stack_init(ks[2], E, (d, e_ff), dtype, scale_in)},
        "we_down": {"kernel": _stack_init(ks[3], E, (e_ff, d), dtype, scale_out)},
    }
    if cfg.num_shared_experts:
        from .mlp import mlp_init as _mi
        p["shared"] = _mi(ks[4], d, e_ff * cfg.num_shared_experts, dtype,
                          "swiglu", cfg.num_layers)
    return p


def _stack_init(key, E, shape, dtype, scale):
    return scale * jax.random.truncated_normal(
        key, -2.0, 2.0, (E,) + shape, jnp.float32).astype(dtype)


def moe_param_axes(cfg) -> Params:
    # router replicated (tiny); expert stacks sharded on the expert (EP) axis
    # only — the shard_map EP path consumes them as local (E_loc, d, f) blocks
    p = {
        "router": {"kernel": (None, None)},
        "we_gate": {"kernel": ("experts", None, None)},
        "we_up": {"kernel": ("experts", None, None)},
        "we_down": {"kernel": ("experts", None, None)},
    }
    if cfg.num_shared_experts:
        from .mlp import mlp_param_axes
        p["shared"] = mlp_param_axes("swiglu")
    return p


def _route(p: Params, cfg, xt: jax.Array):
    """Top-k routing: returns (probs, gate_vals, expert_idx)."""
    E, k = cfg.num_experts, cfg.moe_top_k
    logits = dense_apply(p["router"], xt.astype(jnp.float32))   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)             # (T, k)
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def _local_dispatch(xt, eidx, E: int, cap: int):
    """Local (single-device) capacity dispatch: returns (buf (E,cap,d),
    slot (T·k,), keep (T·k,)).  Pure local scatter — used inside shard_map
    where the partitioner never sees it."""
    Tk = eidx.shape[0]
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos, eidx[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, eidx * cap + pos, E * cap)
    return slot, keep


def moe_apply_ep(p: Params, cfg, x: jax.Array, mesh, dp_axes, ep_axis="model"
                 ) -> tuple:
    """Expert parallelism via shard_map: local capacity dispatch (plain XLA
    scatter on local rows — invisible to the partitioner), ``all_to_all``
    over the EP axis to exchange (device, expert) row blocks, local expert
    matmuls, reverse ``all_to_all``, local combine.  This is the paper's-era
    Switch/GShard schedule expressed with jax-native collectives — the GSPMD
    scatter formulation degenerates to all-gathering every update (measured
    88 s of collectives per step on deepseek-v2-lite, see EXPERIMENTS.md)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    M = mesh.shape[ep_axis]
    E_loc = E // M

    def local_fn(router, wg, wu, wd, x_loc):
        Bl, S_, d_ = x_loc.shape
        Tl = Bl * S_
        xt = x_loc.reshape(Tl, d_)
        probs, gate_vals, expert_idx = _route(
            {"router": {"kernel": router}}, cfg, xt)
        cap = max(4, int(math.ceil(Tl * k / E * cfg.moe_capacity_factor)))
        cap = -(-cap // 8) * 8
        eidx = expert_idx.reshape(Tl * k)
        slot, keep = _local_dispatch(xt, eidx, E, cap)
        token_idx = jnp.repeat(jnp.arange(Tl), k)
        buf = jnp.zeros((E * cap + 1, d_), x_loc.dtype)
        buf = buf.at[slot].set(xt[token_idx], mode="drop")
        # (E, cap, d) -> exchange expert blocks: each peer keeps E_loc experts
        send = buf[:E * cap].reshape(M, E_loc * cap, d_)
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (M, E_loc·cap, d) = rows from every source device
        xs = recv.reshape(M, E_loc, cap, d_).transpose(1, 0, 2, 3) \
            .reshape(E_loc, M * cap, d_)
        wg_, wu_, wd_ = (w.astype(x_loc.dtype) for w in (wg, wu, wd))
        h = jnp.einsum("ecd,edf->ecf", xs, wg_)
        u = jnp.einsum("ecd,edf->ecf", xs, wu_)
        ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd_)
        # reverse exchange: rows return to their source device
        back = ys.reshape(E_loc, M, cap, d_).transpose(1, 0, 2, 3) \
            .reshape(M, E_loc * cap, d_)
        ret = jax.lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False).reshape(E * cap, d_)
        picked = ret[jnp.minimum(slot, E * cap - 1)]
        picked = jnp.where(keep[:, None], picked, 0.0)
        y = (picked.reshape(Tl, k, d_)
             * gate_vals[..., None].astype(x_loc.dtype)).sum(axis=1)
        # load-balance aux (local estimate, mean over DP by symmetry)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[eidx].add(1.0 / (Tl * k))
        aux = cfg.moe_aux_loss * E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return y.reshape(Bl, S_, d_), aux

    P_ = jax.sharding.PartitionSpec
    fn = jax.shard_map(
        local_fn, mesh=mesh, check_vma=False,
        in_specs=(P_(), P_(ep_axis), P_(ep_axis), P_(ep_axis),
                  P_(dp_axes if dp_axes else None)),
        out_specs=(P_(dp_axes if dp_axes else None), P_()))
    y, aux = fn(p["router"]["kernel"], p["we_gate"]["kernel"],
                p["we_up"]["kernel"], p["we_down"]["kernel"], x)
    # name the EP output so remat policies can pin it (save_moe: the backward
    # replay then skips the all-to-alls — collectives are the scarce resource)
    from jax.ad_checkpoint import checkpoint_name
    y = checkpoint_name(y, "moe_out")
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    return constrain(y, "act_batch", "act_seq", "act_embed"), aux


def moe_apply(p: Params, cfg, x: jax.Array) -> tuple:
    """Returns (y, aux_loss).  Dispatches to the shard_map EP path when a
    mesh with a divisible expert axis is active; otherwise runs the local
    scatter path (single device / smoke tests).

    Capacity-based top-k routing with scatter dispatch — O(T·k·d), vs the
    Mesh-TF einsum dispatch whose (T,E,C) one-hot costs O(T²·k·d) at
    training shapes."""
    from ..distributed.sharding import current_mesh, current_rules, shard_factor

    mesh = current_mesh()
    if mesh is not None and cfg.num_experts % mesh.shape.get("model", 1) == 0 \
            and mesh.shape.get("model", 1) > 1:
        rules = current_rules()
        dp_axes = tuple(a for a in rules.get("act_batch", ())
                        if a in mesh.shape and mesh.shape[a] > 1
                        and x.shape[0] % mesh.shape[a] == 0)
        return moe_apply_ep(p, cfg, x, mesh, dp_axes)

    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = dense_apply(p["router"], xt.astype(jnp.float32))   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)             # (T, k)
    if cfg.moe_norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    dp = shard_factor("act_batch", shape=(B,)) or 1             # DP groups
    Tl = T // dp
    cap = max(4, int(math.ceil(Tl * k / E * cfg.moe_capacity_factor)))
    cap = -(-cap // 8) * 8  # lane-align the expert matmul rows

    eidx = expert_idx.reshape(T * k)
    # position of each (token, choice) in its (group, expert) queue
    onehot = jax.nn.one_hot(eidx, E, dtype=jnp.int32).reshape(dp, Tl * k, E)
    pos = jnp.cumsum(onehot, axis=1) - onehot                   # per group
    pos = jnp.take_along_axis(
        pos.reshape(T * k, E), eidx[:, None], axis=1)[:, 0]     # (T·k,)
    keep = pos < cap
    slot = jnp.where(keep, eidx * cap + pos, E * cap)           # overflow bin
    group = jnp.arange(T * k) // (Tl * k)

    token_idx = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((dp, E * cap + 1, d), x.dtype)
    buf = buf.at[group, slot].set(xt[token_idx], mode="drop")
    xs = buf[:, :E * cap].reshape(dp, E, cap, d)
    xs = constrain(xs, "act_group", "act_experts", None, "act_embed")
    h = jnp.einsum("gecd,edf->gecf", xs,
                   p["we_gate"]["kernel"].astype(x.dtype))
    u = jnp.einsum("gecd,edf->gecf", xs,
                   p["we_up"]["kernel"].astype(x.dtype))
    h = jax.nn.silu(h) * u
    h = constrain(h, "act_group", "act_experts", None, "act_mlp_expert")
    ys = jnp.einsum("gecf,efd->gecd", h,
                    p["we_down"]["kernel"].astype(x.dtype))
    ys = constrain(ys, "act_group", "act_experts", None, "act_embed")

    rows = ys.reshape(dp, E * cap, d)
    picked = rows[group, jnp.minimum(slot, E * cap - 1)]        # (T·k, d)
    picked = jnp.where(keep[:, None], picked, 0.0)
    y = (picked.reshape(T, k, d)
         * gate_vals[..., None].astype(x.dtype)).sum(axis=1)    # (T, d)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xt, "swiglu")

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)                                     # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eidx].add(1.0 / (T * k))
    aux = cfg.moe_aux_loss * E * jnp.sum(me * ce)
    y = y.reshape(B, S, d)
    return constrain(y, "act_batch", "act_seq", "act_embed"), aux
