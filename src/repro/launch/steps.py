"""Builders for the jit-able production steps (train / prefill / decode) with
full sharding trees and the rotor remat plan wired in."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import solver_cache
from ..core.chain import Chain
from ..core.devices import device_peaks, hbm_bytes
from ..core.policies import resolve_policy
from ..plan import MemoryPlan, two_tier_fallback
from ..distributed.sharding import (DEFAULT_RULES, LONG_CONTEXT_RULES,
                                    spec_for)
from ..models.flops import stage_flops
from ..models.lm import StagedLM
from ..obs import metrics as obs_metrics
from ..obs.trace import span
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------

def _match_axes(spec_tree: Any, axes_tree: Any):
    """Zip a ShapeDtypeStruct tree with its logical-axes tree (same paths)."""
    sflat = jax.tree_util.tree_flatten_with_path(spec_tree)[0]
    aflat = jax.tree_util.tree_flatten_with_path(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple))[0]
    if len(sflat) != len(aflat):
        raise ValueError(f"axes tree mismatch: {len(sflat)} vs {len(aflat)}")
    for (sp, leaf), (ap, ax) in zip(sflat, aflat):
        if jax.tree_util.keystr(sp) != jax.tree_util.keystr(ap):
            raise ValueError(f"axes path mismatch {sp} vs {ap}")
        yield leaf, ax


def shard_tree(spec_tree: Any, axes_tree: Any, mesh, rules) -> Any:
    """ShapeDtypeStructs annotated with NamedShardings per logical axes."""
    out = []
    for leaf, ax in _match_axes(spec_tree, axes_tree):
        ns = NamedSharding(mesh, spec_for(ax, leaf.shape, mesh, rules))
        out.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=ns))
    treedef = jax.tree_util.tree_structure(spec_tree)
    return jax.tree_util.tree_unflatten(treedef, out)


def sharding_of(tree: Any) -> Any:
    return jax.tree.map(lambda l: l.sharding, tree)


def batch_axes(cfg, kind: str) -> Dict[str, tuple]:
    if kind == "decode":
        tok = (("act_batch", None, None) if cfg.modality == "audio_embed"
               else ("act_batch", None))
        return {"tokens": tok}
    ax: Dict[str, tuple] = {}
    if cfg.modality == "text":
        ax["tokens"] = ("act_batch", "act_seq")
    elif cfg.modality == "audio_embed":
        ax["embeds"] = ("act_batch", "act_seq", None)
    else:
        ax["image_embeds"] = ("act_batch", None, None)
        ax["tokens"] = ("act_batch", "act_seq")
    if kind == "train":
        ax["labels"] = ("act_batch", "act_seq")
        ax["loss_mask"] = ("act_batch", "act_seq")
    return ax


def opt_axes(param_axes: Any) -> Dict[str, Any]:
    return {"mu": param_axes, "nu": param_axes, "count": ()}


# ---------------------------------------------------------------------------
# rotor planning at scale
# ---------------------------------------------------------------------------

def state_bytes(params_spec: Any, n_devices: int) -> float:
    """Per-device bytes of params + grads + Adam moments, assuming full
    (FSDP×TP) sharding of all three (ZeRO-3 via GSPMD)."""
    p_bytes = sum(int(math.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                  for l in jax.tree.leaves(params_spec))
    return p_bytes * (1 + 1 + 4) / n_devices  # bf16 p+g, f32 m+v


def activation_budget_bytes(params_spec: Any, n_devices: int, hbm: int,
                            slack: float = 0.9) -> float:
    """Per-device activation budget = HBM − :func:`state_bytes`.
    ``hbm`` is the planned device's memory (:func:`repro.core.devices
    .hbm_bytes`)."""
    return max(hbm * slack - state_bytes(params_spec, n_devices), hbm * 0.05)


def plan_chain(model: StagedLM, batch_specs: Dict, mesh, rules,
               params_spec: Any = None) -> Chain:
    """Analytic rotor chain for (model × shape × mesh): per-device activation
    sizes from eval_shape ÷ DP shard factor, times from analytic FLOPs over
    the peak of the mesh's device kind.  ``params_spec`` is
    ``jax.eval_shape(model.init, ...)``, traced here when not given."""
    from ..core.planner import profile_stages_analytic

    cfg = model.cfg
    some = next(iter(batch_specs.values()))
    B = some.shape[0]
    S = (batch_specs["tokens"].shape[1] if cfg.modality != "audio_embed"
         else batch_specs["embeds"].shape[1])
    if cfg.modality == "vlm":
        S = S + cfg.prefix_len
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.shape:
            dp *= mesh.shape[ax]
    factor = dp if B % dp == 0 else 1
    fwd, bwd = stage_flops(cfg, B, S)
    if params_spec is None:
        params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stage_specs = model.stage_params(params_spec)
    chain = profile_stages_analytic(
        model.stage_fns(), stage_specs, batch_specs,
        peak_flops=device_peaks(mesh.devices.flat[0]).flops_bf16,
        activation_shard_factor=factor,
        flops_fwd=fwd, flops_bwd=bwd)
    # the head stage's residuals (logits) additionally shard on the model
    # axis when the vocab divides it — fold that into its per-device sizes
    tp = mesh.shape.get("model", 1)
    if tp > 1 and cfg.vocab_size % tp == 0:
        chain.wabar[-1] /= tp
    return chain


def plan_training(model: StagedLM, batch_specs: Dict, mesh, rules,
                  policy: Optional[str] = None, *,
                  num_slots: Optional[int] = None,
                  impl: Optional[str] = None,
                  jit_only: bool = False
                  ) -> Tuple[Optional[MemoryPlan], Optional[Chain]]:
    """Resolve the remat policy for (model × shape × mesh) into a
    :class:`~repro.plan.MemoryPlan` (None = store-all, no remat).

    ``num_slots``/``impl`` thread uniformly into the underlying
    :class:`~repro.plan.PlanRequest` (None = the plan defaults) — this is
    the one place launch-side solver knobs are configured.

    ``jit_only=True`` is the XLA-path contract: host DMA cannot be expressed
    from a remat tree, so an offload-bearing plan is degraded to the best
    two-tier plan at the same device budget (the eager runtime path — see
    ``runtime/train_loop.py`` — runs the true offload schedule instead).

    Profiling the chain and solving are the ``plan.chain`` and
    ``plan.solve`` spans; their seconds, the plan's predicted step time and
    its whole per-device picture of the step (activation peak plus
    :func:`state_bytes`) are the gauges ``plan.chain_s``, ``plan.solve_s``,
    ``plan.predicted_step_s`` and ``plan.planned_bytes``.
    """
    cfg = model.cfg
    policy = policy if policy is not None else cfg.remat_policy
    if policy == "none":
        return None, None
    with span("plan.chain") as chain_span:
        params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        chain = plan_chain(model, batch_specs, mesh, rules, params_spec)
    with span("plan.solve") as solve_span:
        plan = resolve_policy(
            policy, chain, num_slots=num_slots, impl=impl,
            auto_budget=lambda: activation_budget_bytes(
                params_spec, mesh.size, hbm_bytes(mesh.devices.flat[0])))
    if jit_only and plan.uses_offload:
        print("[plan] offload plan needs the host tier; jitted two-tier "
              "fallback at the same device budget", flush=True)
        plan = two_tier_fallback(plan, chain)
    obs_metrics.gauge("plan.chain_s").set(chain_span.seconds)
    obs_metrics.gauge("plan.solve_s").set(solve_span.seconds)
    obs_metrics.gauge("plan.predicted_step_s").set(plan.expected_time)
    obs_metrics.gauge("plan.planned_bytes").set(
        plan.peak_device_mem + state_bytes(params_spec, mesh.size))
    return plan, chain


def plan_rotor_tree(model: StagedLM, batch_specs: Dict, mesh, rules,
                    policy: Optional[str] = None):
    """Back-compat wrapper: resolve the policy into a jit-expressible
    schedule tree (None = store-all).  New code should use
    :func:`plan_training` and keep the full :class:`MemoryPlan`."""
    plan, chain = plan_training(model, batch_specs, mesh, rules, policy,
                                jit_only=True)
    return (plan.tree if plan is not None else None), chain


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def make_train_step(model: StagedLM, opt_cfg: AdamWConfig, tree,
                    lr_fn=None, grad_accum: int = 1):
    """``grad_accum > 1`` scans over microbatches (leading-dim split of the
    global batch), accumulating f32 gradients before one optimizer step —
    the knob the elastic-restart plan uses to keep the global batch constant
    when the data axis shrinks, and the generic lever when per-device
    activation memory is tight even after rotor."""

    def loss_of(p, b):
        return model.loss_fn(p, b, tree=tree)

    def train_step(params, opt_state, batch, step):
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
        else:
            micro = jax.tree.map(
                lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum,
                                    *a.shape[1:]), batch)

            def body(carry, mb):
                lsum, gsum = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                gsum = jax.tree.map(
                    lambda s, x: s + x.astype(jnp.float32), gsum, g)
                return (lsum + l, gsum), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (lsum, gsum), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            loss = lsum / grad_accum
            grads = jax.tree.map(lambda g, p: (g / grad_accum).astype(p.dtype),
                                 gsum, params)
        lr = lr_fn(step) if lr_fn is not None else None
        new_p, new_o, metrics = adamw_update(opt_cfg, grads, opt_state,
                                             params, lr)
        metrics["loss"] = loss
        return new_p, new_o, metrics
    return train_step


def make_prefill_step(model: StagedLM):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_serve_step(model: StagedLM):
    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    return serve_step


# ---------------------------------------------------------------------------
# fully-wired lowering helper (used by dryrun + launch scripts)
# ---------------------------------------------------------------------------

def build_cell(arch_cfg, shape_spec, mesh, policy: Optional[str] = None,
               opt_cfg: Optional[AdamWConfig] = None):
    """Returns (jitted fn, example args as sharded ShapeDtypeStructs)."""
    from ..configs.shapes import input_specs

    from ..distributed.sharding import DECODE_RULES

    cfg = arch_cfg
    model = StagedLM(cfg)
    if shape_spec.name == "long_500k":
        rules = LONG_CONTEXT_RULES
    elif shape_spec.kind in ("decode", "prefill"):
        rules = DECODE_RULES
    else:
        rules = DEFAULT_RULES
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sds = shard_tree(params_spec, model.param_axes(), mesh, rules)
    batch_specs = input_specs(cfg, shape_spec)
    batch_sds = shard_tree(batch_specs, batch_axes(cfg, shape_spec.kind),
                           mesh, rules)

    if shape_spec.kind == "train":
        plan, chain = plan_training(model, batch_specs, mesh, rules, policy,
                                    jit_only=True)
        tree = plan.tree if plan is not None else None
        st = solver_cache.stats()
        if st["hits"] or st["misses"]:
            # repeated launches and budget sweeps are served from the
            # persistent solver cache — the DP fill is skipped on hits
            print(f"[rotor] solver cache: {st['hits']} hits / "
                  f"{st['misses']} misses ({st['disk_hits']} from disk)",
                  flush=True)
        opt_cfg = opt_cfg or AdamWConfig()
        opt_spec = jax.eval_shape(adamw_init, params_spec)
        opt_sds = shard_tree(opt_spec, opt_axes(model.param_axes()), mesh,
                             rules)
        step_sds = jax.ShapeDtypeStruct((), jnp.int32,
                                        sharding=NamedSharding(mesh, P()))
        fn = make_train_step(model, opt_cfg, tree)
        rep = NamedSharding(mesh, P())
        out_shardings = (sharding_of(params_sds), sharding_of(opt_sds),
                         {"loss": rep, "grad_norm": rep, "param_norm": rep})
        jitted = jax.jit(fn, donate_argnums=(0, 1),
                         out_shardings=out_shardings)
        args = (params_sds, opt_sds, batch_sds, step_sds)
        return jitted, args, rules, {"tree": tree, "chain": chain,
                                     "plan": plan}

    if shape_spec.kind == "prefill":
        fn = make_prefill_step(model)
        cache_spec = jax.eval_shape(
            functools.partial(model.init_cache, shape_spec.global_batch,
                              shape_spec.seq_len))
        cache_shard = sharding_of(shard_tree(cache_spec, model.cache_axes(),
                                             mesh, rules))
        rep = NamedSharding(mesh, P())
        logits_shard = rep
        jitted = jax.jit(fn, out_shardings=(logits_shard, cache_shard))
        return jitted, (params_sds, batch_sds), rules, {}

    # decode
    fn = make_serve_step(model)
    cache_spec = jax.eval_shape(
        functools.partial(model.init_cache, shape_spec.global_batch,
                          shape_spec.seq_len))
    cache_sds = shard_tree(cache_spec, model.cache_axes(), mesh, rules)
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(fn, donate_argnums=(1,),
                     out_shardings=(rep, sharding_of(cache_sds)))
    return jitted, (params_sds, cache_sds, batch_sds["tokens"]), rules, {}
