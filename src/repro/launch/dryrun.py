import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=" + os.environ.get("REPRO_DRYRUN_DEVICES", "512") + " --xla_disable_hlo_passes=optimization-barrier-expander,cse,dot-merger").strip()
# The disable_hlo_passes keep jax.checkpoint's optimization barriers alive on
# the CPU backend so compiled FLOPs honestly include rematerialization
# recompute (the TPU backend preserves remat without these; CPU strips it and
# CSEs the recompute away — see DESIGN.md §Dry-run-on-CPU caveats).

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and record memory/cost/collective analysis (§Dry-run).

MUST be run as a script / subprocess (the XLA_FLAGS line above executes
before any jax import — jax locks the device count on first init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b \
      --shape train_4k [--multi-pod] [--policy rotor:auto] [--out DIR]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy: str | None, out_dir: str, overrides: dict | None = None,
             tag: str = "") -> dict:
    import jax
    from ..configs import get_config
    from ..configs.shapes import SHAPES, applicable
    from ..distributed.sharding import axis_rules
    from ..launch.mesh import make_production_mesh
    from ..launch.roofline import analyze
    from ..launch.steps import build_cell
    from ..models.flops import model_flops_per_step

    assert applicable(arch, shape_name), f"{arch} × {shape_name} not assigned"
    t0 = time.time()
    cfg = get_config(arch, **(overrides or {}))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    with axis_rules(mesh):
        jitted, args, rules, extra = build_cell(cfg, shape, policy=policy,
                                                mesh=mesh)
        with axis_rules(mesh, rules):
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    print(compiled.memory_analysis())   # proves it fits
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed")
           if ca and k in ca})          # FLOPs/bytes for §Roofline
    hlo = compiled.as_text()
    mf = model_flops_per_step(cfg, shape.global_batch,
                              1 if shape.kind == "decode" else shape.seq_len,
                              train=(shape.kind == "train"))
    roof = analyze(compiled, mesh.size, mf, hlo_text=hlo)

    # model-based per-device peak (the number that must fit 16 GiB): the CPU
    # backend's buffer assignment is not memory-minimizing (no remat-aware
    # scheduling), so memory_analysis is an un-scheduled upper bound; the
    # rotor simulator gives the exact model peak for the planned schedule.
    model_mem = None
    if extra.get("chain") is not None:
        from ..core.schedule import Schedule, simulate
        from ..core.solver import tree_to_schedule
        chain = extra["chain"]
        sched = (tree_to_schedule(extra["tree"], chain.length)
                 if extra.get("tree") is not None
                 else Schedule.store_all(chain.length))
        act_peak = simulate(chain, sched).peak_mem
        import jax as _jax
        import numpy as _np
        from ..models.lm import StagedLM
        pspec = _jax.eval_shape(StagedLM(cfg).init, _jax.random.PRNGKey(0))
        p_bytes = sum(int(_np.prod(l.shape)) * _np.dtype(l.dtype).itemsize
                      for l in _jax.tree.leaves(pspec))
        states = p_bytes * 6 / mesh.size  # bf16 p+g, f32 m+v (ZeRO-3 sharded)
        model_mem = {"activation_peak_bytes": float(act_peak),
                     "param_opt_grad_bytes": float(states),
                     "total_bytes": float(act_peak + states)}

    # analytic roofline terms (primary: immune to HloCostAnalysis's
    # while-body-once counting; see launch/analytic.py docstring)
    from ..launch.analytic import decode_terms, prefill_terms, train_terms
    from ..models.lm import StagedLM as _SLM
    from ..core.solver import tree_to_schedule as _t2s
    _model = _SLM(cfg)
    if shape.kind == "train":
        _sched = (_t2s(extra["tree"], extra["chain"].length)
                  if extra.get("tree") is not None else None)
        analytic = train_terms(cfg, shape, mesh, _model, extra["chain"],
                               _sched)
    elif shape.kind == "decode":
        analytic = decode_terms(cfg, shape, mesh, _model)
    else:
        analytic = prefill_terms(cfg, shape, mesh, _model)
    terms = {k: analytic[k] for k in ("compute_s", "memory_s", "collective_s")}
    analytic["dominant"] = max(terms, key=terms.get).replace("_s", "")

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": ("2x16x16" if multi_pod else "16x16"),
        "n_devices": mesh.size,
        "analytic": analytic,
        "policy": policy or cfg.remat_policy,
        "overrides": overrides or {},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                              + ma.output_size_in_bytes - ma.alias_size_in_bytes),
            "model_peak": model_mem,
        },
        "roofline": roof.to_json(),
        "rotor": None,
        # the full planning artifact (strategy, budget, predicted makespan,
        # device/host peaks, op counts) — repro.plan.MemoryPlan.stats()
        "plan": (extra["plan"].stats() if extra.get("plan") is not None
                 else None),
    }
    # process-wide observability counters accumulated while planning and
    # compiling this cell (solver cache traffic, DP fill wall times, ...)
    from ..obs import metrics as _obs_metrics
    rec["metrics"] = _obs_metrics.snapshot()
    if extra.get("tree") is not None:
        from ..core.rematerialize import count_checkpoint_scopes
        rec["rotor"] = {"ck_scopes": count_checkpoint_scopes(extra["tree"])}
    name = f"{arch}__{shape_name}__{rec['mesh']}{tag}.json"
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    from ..core.devices import V5E
    hbm = V5E.hbm_bytes
    print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
          f"peak={rec['memory']['peak_bytes']/2**30:.2f} GiB/dev "
          f"({'FITS' if rec['memory']['peak_bytes'] <= hbm else 'OVER'} 16GiB) "
          f"dominant={roof.dominant} "
          f"terms(c/m/x)=({roof.compute_s:.4f}/{roof.memory_s:.4f}/"
          f"{roof.collective_s:.4f})s lower={t_lower:.0f}s "
          f"compile={t_compile:.0f}s", flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="rotor:auto",
                    help="remat policy for train cells (rotor:auto = the "
                         "paper's optimal persistent schedule under the "
                         "per-device activation budget; none|full|periodic:K)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf iters)")
    ap.add_argument("--tag", default="", help="suffix for the output file")
    args = ap.parse_args(argv)

    overrides = json.loads(args.override) if args.override else None
    from ..configs import ARCHS
    from ..configs.shapes import SHAPES, applicable

    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCHS for s in SHAPES if applicable(a, s)])
    failures = 0
    for arch, shape in cells:
        try:
            run_cell(arch, shape, args.multi_pod, args.policy, args.out,
                     overrides, args.tag)
        except Exception:
            failures += 1
            print(f"[dryrun] FAILED {arch} × {shape}", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
