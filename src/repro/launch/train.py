"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains reduced configs end-to-end; on a TPU pod the
same entrypoint builds the (pod, data, model) mesh from the slice topology
and runs the identical code path (shardings flow from the logical rules).

Recommended production XLA flags (recorded here; they are TPU-only):
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
  --xla_tpu_overlap_compute_collective_tc=true
  --xla_enable_async_all_gather=true
(compute/communication overlap for the FSDP all-gathers and DP reduces.)
"""

from __future__ import annotations

import argparse
import json

import jax

from ..configs import get_config, smoke_config
from ..distributed.fault_tolerance import elastic_plan
from ..runtime.train_loop import TrainLoopConfig, run_training
from .compile_cache import enable_compile_cache
from .mesh import make_mesh


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--policy", default=None,
                    help="remat policy: none|full|periodic:K|rotor:auto|"
                         "rotor:BYTES|revolve:BYTES|"
                         "optimal_offload:BYTES[:BW] (each maps onto a "
                         "repro.plan.PlanRequest — see README 'Planning "
                         "API')")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="DP discretization slots (default: plan default)")
    ap.add_argument("--solver-impl", default=None,
                    choices=("banded", "pallas", "pallas_fused", "reference"),
                    help="DP fill kernels: banded numpy, the per-band Pallas"
                         " kernel, the fused single-dispatch Pallas fill"
                         " (both compiled for a TPU), or the seed"
                         " float64 path (default: banded / REPRO_DP_IMPL)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--override", default=None, help="JSON config overrides")
    args = ap.parse_args(argv)
    enable_compile_cache()

    ov = json.loads(args.override) if args.override else {}
    cfg = smoke_config(args.arch, **ov) if args.smoke else get_config(args.arch, **ov)

    n = len(jax.devices())
    (data, model_par), axes, accum = elastic_plan(n, args.model_parallel,
                                                  args.global_batch)
    mesh = make_mesh((data, model_par), axes)
    print(f"[train] arch={cfg.name} mesh={dict(mesh.shape)} "
          f"devices={n} accum={accum}")

    loop = TrainLoopConfig(steps=args.steps, global_batch=args.global_batch,
                           seq_len=args.seq_len, lr=args.lr,
                           policy=args.policy, num_slots=args.num_slots,
                           solver_impl=args.solver_impl,
                           ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every)
    out = run_training(cfg, loop, mesh=mesh)
    print(f"[train] done: {len(out['losses'])} steps, "
          f"loss {out['losses'][0]:.4f} -> {out['losses'][-1]:.4f}, "
          f"{out['tokens_per_s']:.0f} tok/s after the first step")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
