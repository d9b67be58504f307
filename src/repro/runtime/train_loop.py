"""Production training loop: rotor-planned remat, checkpoint/restart,
straggler watchdog, deterministic data resume, optional int8 gradient
compression on the DP axes.

This is the same driver for a 1-chip CPU run and a 512-chip pod run — only
the mesh differs; every sharding flows from the logical-axis rules.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ckpt.manager import CheckpointManager
from ..core.rematerialize import count_checkpoint_scopes
from ..data.pipeline import SyntheticLMData
from ..distributed.fault_tolerance import StragglerWatchdog
from ..distributed.sharding import DEFAULT_RULES, axis_rules
from ..launch.roofline import dot_shapes_from_hlo
from ..launch.steps import (batch_axes, make_train_step, opt_axes,
                            plan_training, shard_tree, sharding_of)
from ..models.lm import StagedLM
from ..obs import metrics as obs_metrics
from ..obs.trace import span
from ..optim.adamw import AdamWConfig, adamw_init, adamw_update
from ..optim.schedules import linear_warmup_cosine


def _record_step_bytes(compiled) -> None:
    """Set ``train.step_bytes``: the compiled step's per-device bytes,
    arguments + outputs − aliased (donated) + temporaries, from
    ``memory_analysis()`` (left unset where the backend gives none)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return
    obs_metrics.gauge("train.step_bytes").set(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _record_vocab_dots(compiled, vocab_size: int) -> None:
    """Set ``train.vocab_dots``: the matmuls in the compiled step's HLO whose
    result has a vocabulary-sized dimension (the head's logits and weight
    gradient, and any recompute of them)."""
    text = compiled.as_text()
    if text:
        obs_metrics.gauge("train.vocab_dots").set(sum(
            vocab_size in shape for shape in dot_shapes_from_hlo(text)))


def _make_offload_step(model, opt_cfg: AdamWConfig, schedule, lr_fn,
                       tracer=None):
    """Eager train step for a three-tier (host-offload) schedule: gradients
    come from the op-faithful offload executor — ``jax.device_put`` copies and
    all — and only the optimizer update is jitted.  This is the path where
    the solver's host tier is real, not a remat approximation.  ``tracer``
    (opt-in) records one span per schedule op every step."""
    from ..offload.executor import execute_offload_schedule
    from ..offload.host_buffer import HostBuffer

    stage_fns = model.stage_fns()

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def upd(grads, opt_state, params, lr):
        return adamw_update(opt_cfg, grads, opt_state, params, lr)

    def step_fn(params, opt_state, batch, step):
        sp = model.stage_params(params)
        loss, stage_grads, _ = execute_offload_schedule(
            schedule, stage_fns, sp, batch, host_buffer=HostBuffer(),
            tracer=tracer)
        grads = model.combine_stage_grads(stage_grads)
        lr = lr_fn(step) if lr_fn is not None else None
        new_p, new_o, metrics = upd(grads, opt_state, params, lr)
        metrics["loss"] = loss
        return new_p, new_o, metrics

    return step_fn


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    seed: int = 0
    lr: float = 3e-4
    warmup: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    ckpt_keep: int = 3
    async_ckpt: bool = True
    log_every: int = 10
    policy: Optional[str] = None        # remat policy override
    num_slots: Optional[int] = None     # DP discretization (None = plan default)
    solver_impl: Optional[str] = None   # DP kernels (dp_kernels.KNOWN_IMPLS)
    grad_accum: int = 1                 # microbatch accumulation factor
    straggler_threshold: float = 3.0
    data_host_count: int = 1
    data_host_index: int = 0
    trace_path: Optional[str] = None    # write a Perfetto trace.json here


def run_training(cfg, loop: TrainLoopConfig, mesh=None,
                 log_fn: Callable[[str], None] = print,
                 tracer=None) -> Dict[str, Any]:
    """Train a StagedLM; returns final metrics + state handles.

    Each iteration is a ``train.step`` span (the profiler's step marker)
    holding ``train.data_wait`` (the prefetch queue), ``train.device_put``
    (the batch transfer), ``train.dispatch`` (the step's call) and
    ``train.sync`` (``float(loss)``, the host waiting for the device), each
    with ``step=k`` (:func:`repro.obs.trace.span`).  The jitted step is
    lowered and compiled once, on the first batch, inside ``train.compile``
    (the jit's own calls then find that executable); the compiled step's
    per-device bytes (arguments + outputs − aliased + temporaries) are the
    gauge ``train.step_bytes``, and its matmuls with a vocabulary-sized
    result the gauge ``train.vocab_dots``.

    ``tracer`` (a :class:`repro.obs.trace.Tracer`, opt-in) records those
    spans too, and per-op spans on the eager offload path; when the offload
    executor ran traced, the result dict gains a ``drift`` report comparing
    the plan's predicted makespan against the last (warmest) traced step.
    ``tokens_per_s`` counts the steps after the first, from the end of the
    first step (which compiles) to the end of the last.
    """
    from ..configs.shapes import ShapeSpec, input_specs

    if tracer is None and loop.trace_path:
        from ..obs.trace import Tracer
        tracer = Tracer(name="train")

    model = StagedLM(cfg)
    if mesh is None:
        from ..launch.mesh import make_mesh
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    rules = DEFAULT_RULES
    opt_cfg = AdamWConfig(lr=loop.lr)
    lr_fn = linear_warmup_cosine(loop.lr, loop.warmup, loop.steps)

    shape = ShapeSpec("train", "train", loop.seq_len, loop.global_batch)
    with axis_rules(mesh, rules):
        batch_specs = input_specs(cfg, shape)
        # one planning entry point for every policy — the plan itself says
        # which executor it needs (no policy-string dispatch here)
        plan, chain = plan_training(model, batch_specs, mesh, rules,
                                    loop.policy, num_slots=loop.num_slots,
                                    impl=loop.solver_impl)
        offload_plan, tree = None, None
        if plan is not None and plan.uses_offload:
            if loop.grad_accum != 1:
                raise NotImplementedError(
                    "grad_accum > 1 with an offload schedule")
            if mesh.size > 1:
                # the eager executor commits prefetched activations to a
                # single device; mesh-sharded params/batch would mix
                # incompatible placements
                raise NotImplementedError(
                    "the optimal_offload eager path runs on a single "
                    "device; use a two-tier policy (rotor:...) on "
                    "multi-device meshes")
            offload_plan = plan
            log_fn(f"[offload] three-tier plan: "
                   f"{plan.schedule.count('Foff')} host offloads, "
                   f"predicted {plan.expected_time:.4f}s model "
                   f"time/step — eager executor engaged")
        elif plan is not None:
            tree = plan.tree
        if tree is not None:
            budget = ("" if plan.budget_bytes is None else
                      f", device budget {plan.budget_bytes / 2**30:.3f} GiB")
            log_fn(f"[rotor] plan: {count_checkpoint_scopes(tree)} checkpoint "
                   f"scopes over {model.n_stages()} stages{budget}")
        from ..core import solver_cache
        st = solver_cache.stats()
        if st["hits"] or st["misses"]:
            log_fn(f"[plan] solver cache: {st['hits']} hits / "
                   f"{st['misses']} misses — identical relaunches skip the "
                   f"DP fill")
        if offload_plan is not None:
            step_fn = _make_offload_step(model, opt_cfg,
                                         offload_plan.schedule, lr_fn,
                                         tracer=tracer)
        else:
            step_fn = jax.jit(make_train_step(model, opt_cfg, tree, lr_fn,
                                              grad_accum=loop.grad_accum),
                              donate_argnums=(0, 1))
        # the jitted step is compiled ahead of the first step, on its batch
        compile_first = offload_plan is None

        params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(loop.seed))
        p_shard = sharding_of(shard_tree(params_spec, model.param_axes(),
                                         mesh, rules))
        o_spec = jax.eval_shape(adamw_init, params_spec)
        o_shard = sharding_of(shard_tree(o_spec, opt_axes(model.param_axes()),
                                         mesh, rules))
        b_shard = sharding_of(shard_tree(batch_specs,
                                         batch_axes(cfg, "train"), mesh, rules))

        manager = (CheckpointManager(loop.ckpt_dir, keep=loop.ckpt_keep)
                   if loop.ckpt_dir else None)
        start_step = 0
        if manager is not None and manager.latest_step() is not None:
            target = {"params": params_spec, "opt": o_spec,
                      "step": jax.ShapeDtypeStruct((), jnp.int32)}
            shards = {"params": p_shard, "opt": o_shard, "step": None}
            s, state = manager.restore(target, shardings=shards)
            params, opt_state = state["params"], state["opt"]
            start_step = int(state["step"]) + 1
            log_fn(f"[ckpt] restored step {s}; resuming at {start_step}")
        else:
            params = jax.jit(model.init, out_shardings=p_shard)(
                jax.random.PRNGKey(loop.seed))
            opt_state = jax.jit(adamw_init, out_shardings=o_shard)(params)

        data = SyntheticLMData(cfg, loop.global_batch, loop.seq_len,
                               seed=loop.seed,
                               host_index=loop.data_host_index,
                               host_count=loop.data_host_count)
        data.start(from_step=start_step)
        watchdog = StragglerWatchdog(threshold=loop.straggler_threshold)
        losses, grad_norms, step_seconds = [], [], []
        t_first = t_last = None  # ends of the first and the last step
        step = start_step
        try:
            for step in range(start_step, loop.steps):
                with span("train.step", tracer, step=step, marks_step=True):
                    watchdog.step_begin()
                    with span("train.data_wait", tracer, step=step):
                        host_batch = data.next()
                    with span("train.device_put", tracer, step=step):
                        batch = jax.tree.map(
                            lambda arr, shd: jax.device_put(arr, shd),
                            host_batch, b_shard)
                    step_arr = jnp.asarray(step, jnp.int32)
                    if compile_first:
                        with span("train.compile", tracer, step=step):
                            compiled = step_fn.lower(params, opt_state, batch,
                                                     step_arr).compile()
                        _record_step_bytes(compiled)
                        _record_vocab_dots(compiled, cfg.vocab_size)
                        compile_first = False
                    t_step = time.perf_counter()
                    with span("train.dispatch", tracer, step=step):
                        params, opt_state, metrics = step_fn(
                            params, opt_state, batch, step_arr)
                    with span("train.sync", tracer, step=step):
                        loss = float(metrics["loss"])  # waits for the step
                    t_last = time.perf_counter()
                    step_s = t_last - t_step
                    if t_first is None:
                        t_first = t_last
                    obs_metrics.histogram("train.step_seconds").observe(step_s)
                    obs_metrics.gauge("train.loss").set(loss)
                    losses.append(loss)
                    grad_norms.append(float(metrics["grad_norm"]))
                    step_seconds.append(step_s)
                    ev = watchdog.step_end(step)
                    if ev is not None:
                        log_fn(f"[watchdog] straggler at step {ev.step}: "
                               f"{ev.duration:.2f}s vs median {ev.median:.2f}s")
                    if watchdog.should_restart:
                        log_fn("[watchdog] persistent straggler — "
                               "checkpointing for restart")
                        if manager is not None:
                            manager.save(step, {"params": params,
                                                "opt": opt_state,
                                                "step": step_arr},
                                         blocking=True)
                        break
                    if step % loop.log_every == 0:
                        log_fn(f"step {step:5d} loss {loss:.4f} "
                               f"gnorm {grad_norms[-1]:.3f} "
                               f"time {step_s:.3f}s")
                    if (manager is not None and loop.ckpt_every
                            and step and step % loop.ckpt_every == 0):
                        manager.save(step, {"params": params,
                                            "opt": opt_state,
                                            "step": step_arr},
                                     blocking=not loop.async_ckpt)
        finally:
            data.stop()
            if manager is not None:
                manager.wait()
        if manager is not None:
            manager.save(step, {"params": params, "opt": opt_state,
                                "step": jnp.asarray(step, jnp.int32)},
                         blocking=True)
        # the steps after the first, over the time they took: the first
        # step's compile is not throughput
        timed = len(losses) - 1
        tokens_per_s = (loop.global_batch * loop.seq_len * timed
                        / (t_last - t_first) if timed > 0 else float("nan"))
        result = {"losses": losses, "grad_norms": grad_norms,
                  "step_seconds": step_seconds,
                  "plan": plan, "params": params, "opt_state": opt_state,
                  "last_step": step, "tokens_per_s": tokens_per_s,
                  "straggler_events": len(watchdog.events)}
        if tracer is not None and tracer.spans:
            if loop.trace_path:
                tracer.save(loop.trace_path)
                log_fn(f"[obs] wrote {len(tracer.spans)} spans to "
                       f"{loop.trace_path}")
            if offload_plan is not None:
                # drift vs the last (warmest) step's schedule ops — earlier
                # steps carry one-time jit/transfer warm-up costs, and the
                # loop's own train.* spans are no schedule op
                from ..obs.drift import compare
                from ..obs.trace import Tracer as _Tracer
                kinds = {k for k, _ in offload_plan.schedule.ops}
                ops = [s for s in tracer.spans if s.op in kinds]
                last = _Tracer(name="train-last-step")
                last.spans.extend(ops[-len(offload_plan.schedule):])
                report = compare(offload_plan, last)
                log_fn(f"[obs] {report.summary()}")
                result["drift"] = report
        return result
