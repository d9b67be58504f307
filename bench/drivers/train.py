"""Training cells: one call of ``repro.runtime.train_loop.run_training``
from the seed, under the cell's remat policy.

The call's first steps are set-up: they compile the step and are the
steps the reference checks.  The window then runs from the end of step
``warm_steps - 1`` to the end of the first step that ends at least
``--seconds`` later, timed on the benchmark's clock from the loop's
per-step log callback (``log_every=1``).  At both ends the callback waits
for the loop's parameters and optimizer state, so the window holds all
the work of its steps even where the loop dispatches ahead of them.  The
callback then raises, which ends the loop through its own clean-up.

What the check compares is read from the loop's own state in the
callback's calling frame, at the end of step 0 (the optimizer state after
one step, and the gradient norm it reported) and at the end of step
``check_steps - 1`` (the parameters as the next step gets them).  The
check needs the state of the window's own compiled step between its
steps, which ``run_training`` returns only at its end; a second call
would build a second step.  A loop that stops keeping these names fails
the run loudly.
"""

from __future__ import annotations

import math
import sys
import time

import numpy as np

#: ``run_training`` runs until the window closes; this only bounds it.
MAX_STEPS = 1_000_000


class _WindowClosed(Exception):
    """Raised from the log callback to end ``run_training``."""


def _loop_state(names):
    """The named locals of the ``run_training`` frame that called the log
    callback (two frames up: this helper, then the callback)."""
    frame = sys._getframe(2)
    if frame.f_code.co_name != "run_training":
        return None
    loc = frame.f_locals
    if "losses" not in loc:
        return None  # a log line before the loop
    missing = [n for n in names if n not in loc]
    if missing:
        raise RuntimeError(f"run_training no longer keeps {missing} in its "
                           f"loop; the benchmark reads them for its check")
    return {n: loc[n] for n in names}


class _Recorder:
    """The log callback: times steps, captures what the check needs, opens
    and closes the window."""

    def __init__(self, ctx, warm: int, check_steps: int):
        import jax
        import jax.numpy as jnp

        self.ctx = ctx
        self.warm = warm
        self.check_steps = check_steps
        self.n = 0
        self.t_start = self.t_end = None
        self.setup_s = None
        self.mu_norms = self.mu_paths = None
        self.gnorm0 = None
        self.params_host = None
        self.plan_scopes = None
        self.losses = None
        self._norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])

    def __call__(self, msg: str) -> None:
        st = _loop_state(("losses", "grad_norms", "params", "opt_state", "plan"))
        if st is None or len(st["losses"]) == self.n:
            return
        with self.ctx.span("bench.step_callback"):
            self._step_end(st)

    def _step_end(self, st) -> None:
        import jax

        now = time.perf_counter()
        self.n = len(st["losses"])
        k = self.n - 1
        if k == 0:
            mu = st["opt_state"]["mu"]
            self.mu_paths = [jax.tree_util.keystr(p) for p, _ in
                             jax.tree_util.tree_flatten_with_path(mu)[0]]
            self.mu_norms = np.asarray(jax.device_get(self._norms(mu)),
                                       np.float64)
            self.gnorm0 = float(st["grad_norms"][0])
            plan = st["plan"]
            if plan is not None and getattr(plan, "tree", None) is not None:
                from repro.core.rematerialize import count_checkpoint_scopes

                self.plan_scopes = count_checkpoint_scopes(plan.tree)
        if k == self.check_steps - 1:
            self.params_host = jax.device_get(st["params"])
        if k == self.warm - 1:
            # the window starts once the set-up steps' work is done, and
            # ends once the last window step's is, whether or not the loop
            # itself waits for each step
            jax.block_until_ready((st["params"], st["opt_state"]))
            self.setup_s = time.perf_counter() - self.ctx.t0
            self.t_start = self.ctx.window_open()
        elif k >= self.warm and now - self.t_start >= self.ctx.window_seconds:
            jax.block_until_ready((st["params"], st["opt_state"]))
            self.t_end = self.ctx.window_close()
            self.losses = [float(x) for x in st["losses"]]
            raise _WindowClosed


def run(ctx) -> dict:
    import jax

    from repro.runtime.train_loop import TrainLoopConfig, run_training

    from bench import flops
    from bench.reference import compare
    from bench.reference.data import batch_at
    from bench.reference.dense import Reference, follow, lr_at

    t = ctx.traffic
    opt = ctx.config["optimizer"]
    check_steps, warm = int(t["check_steps"]), int(t["warm_steps"])
    if not 1 <= check_steps <= warm:
        raise ValueError("need 1 <= check_steps <= warm_steps")
    B, S = int(t["global_batch"]), int(t["seq_len"])
    loop = TrainLoopConfig(steps=MAX_STEPS, global_batch=B, seq_len=S,
                           seed=ctx.prog_seed, lr=opt["lr"],
                           warmup=opt["warmup"], log_every=1,
                           policy=t["policy"])
    rec = _Recorder(ctx, warm, check_steps)
    cfg = ctx.model_config()
    with ctx.span("bench.run_training"):
        try:
            run_training(cfg, loop, mesh=ctx.mesh(), log_fn=rec)
        except _WindowClosed:
            pass
    if rec.t_end is None:
        raise RuntimeError(f"run_training stopped after {rec.n} steps, before "
                           f"the window closed")
    window_steps = rec.n - warm
    window_s = rec.t_end - rec.t_start
    tokens = window_steps * B * S
    memory_peak = ctx.memory_peak()
    del rec._norms
    losses = rec.losses
    failed = sum(1 for x in losses if not math.isfinite(x))
    print(f"[train] {ctx.name}: batch {B} x seq {S}, plan "
          f"{rec.plan_scopes} checkpoint scopes; {window_steps} steps in "
          f"{window_s:.4f} s; setup {rec.setup_s:.3f} s; losses "
          f"{losses[:check_steps]}", file=sys.stderr, flush=True)

    # the check: the reference follows the first steps from the same seed,
    # once the program's state is gone
    t_ref = time.perf_counter()
    ref = Reference(ctx.model)
    batches = [batch_at(ctx.model["vocab_size"], B, S, ctx.prog_seed, k)
               for k in range(check_steps)]
    lrs = [lr_at(k, opt["lr"], opt["warmup"], MAX_STEPS)
           for k in range(check_steps)]
    r = follow(ref, opt, jax.random.PRNGKey(ctx.prog_seed), batches, lrs)
    if r["paths"] != rec.mu_paths:
        raise RuntimeError("the program's parameter tree no longer matches "
                           "the reference's")
    scale0 = 1.0
    if opt.get("clip_norm") is not None:
        scale0 = min(1.0, opt["clip_norm"] / max(rec.gnorm0, 1e-12))
    prog = {"losses": losses,
            "grad0": rec.mu_norms / (1.0 - opt["b1"]) / scale0,
            "delta": _delta_norms(rec.params_host, r["init"])}
    del r["init"]
    nums = compare.train_numbers(prog, r)
    print(f"[train] reference: {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr, flush=True)
    print(f"[train] worst leaves: {nums['where']}", file=sys.stderr, flush=True)
    for i, path in enumerate(r["paths"]):  # program vs reference, per leaf
        print(f"[leaf] {path} grad0 {float(prog['grad0'][i])!r} ref "
              f"{float(r['grad0'][i])!r} delta {float(prog['delta'][i])!r} "
              f"ref {float(r['delta'][i])!r}", file=sys.stderr, flush=True)
    checks = {k: {"value": nums[k], "limit": ctx.limits[k]}
              for k in ("loss", "grad", "update")}
    fpt = flops.train_flops_per_token(ctx.model, S)
    return {
        "e2e": {"train_tokens_per_s": tokens / window_s,
                "setup_s": rec.setup_s},
        "attempted": window_steps, "failed": failed, "checks": checks,
        "memory_peak_bytes": int(memory_peak), "window_s": window_s,
        "window_compiles": ctx.compiles.between(rec.t_start, rec.t_end),
        "model_flops": tokens * fpt, "chips": len(ctx.devices),
        "device_kind": ctx.devices[0].device_kind,
        "plan_scopes": rec.plan_scopes, "worst_leaves": nums["where"],
    }


def _delta_norms(host_params, init) -> np.ndarray:
    """Per-leaf norm of (program's parameters - initial parameters)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return np.asarray([float(f(jnp.asarray(a), b)) for a, b in
                       zip(jax.tree.leaves(host_params), jax.tree.leaves(init))],
                      np.float64)
