"""End-to-end driver: train a ~100M-parameter decoder LM with the rotor remat
policy, checkpoint/restart, straggler watchdog and deterministic data.

Default sizing (~104M params: d=640, 10 layers, vocab 16384) is real work on
a CPU; use --tiny for a fast demonstration.  Kill it mid-run and re-invoke
with the same --ckpt-dir to watch it resume from the checkpoint.

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300 \
          --ckpt-dir /tmp/rotor_lm_ckpt
"""

import argparse

import jax.numpy as jnp

from repro.configs import smoke_config
from repro.runtime.train_loop import TrainLoopConfig, run_training


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--policy", default="rotor:x0.6",
                    help="activation budget: 60%% of the store-all peak "
                         "(any repro.plan policy works, e.g. "
                         "optimal_offload:x0.4)")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="DP discretization slots (default: plan default)")
    args = ap.parse_args()

    if args.tiny:
        cfg = smoke_config("qwen1.5-4b")
        batch, seq = 8, 64
    else:
        cfg = smoke_config(
            "qwen1.5-4b", num_layers=10, layer_kinds=("dense",) * 10,
            d_model=640, n_heads=10, n_kv_heads=10, head_dim=64,
            d_ff=2560, vocab_size=16384, n_chunks=10,
            dtype=jnp.float32, param_dtype=jnp.float32)
        batch, seq = 2, 128
    n = cfg.total_params()
    print(f"[example] {cfg.name}-derived LM: {n/1e6:.1f}M params, "
          f"policy={args.policy}")

    loop = TrainLoopConfig(steps=args.steps, global_batch=batch, seq_len=seq,
                           lr=1e-3, warmup=20, policy=args.policy,
                           num_slots=args.num_slots,
                           ckpt_dir=args.ckpt_dir, ckpt_every=50,
                           log_every=10)
    out = run_training(cfg, loop)
    print(f"[example] loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f} "
          f"over {len(out['losses'])} steps; "
          f"{out['tokens_per_s']:.0f} tokens/s after the first step")


if __name__ == "__main__":
    main()
