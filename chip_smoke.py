"""Smoke run of the training and serving entry points on a TPU, at published
widths.

    python chip_smoke.py             # one chip: train 3 steps, then decode
    python chip_smoke.py --chips 4   # the sharded training path on a
                                     # (data=2, model=2) mesh vs one chip

The model is qwen1.5-4b with every width as published (d_model 2560, 20
heads, d_ff 6912, vocab 151936, bf16) and its depth cut to 4 layers: about
1.1 B parameters, whose weights, gradients and f32 Adam moments take ~13 GB
of the chip's 16 GB.  Training goes through ``run_training`` under the
``rotor:auto`` plan; decoding goes through ``run_serving`` from the trained
weights and is checked against the model's full forward pass.

Each phase prints what it measured on earlier lines.  Any failed check or
exception exits non-zero; so does a machine where JAX finds no TPU (there is
no CPU fallback).  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

One process drives the chip(s): this script starts no child processes.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen1.5-4b"
DEPTH = 4
STEPS = 3
SEQ_LEN = 2048
GLOBAL_BATCH = 4
POLICY = "rotor:auto"
#: Every step updates the weights.  Under the loop's default 10-step warmup
#: the learning rate is 0 at step 0 and 3e-5 at step 1, which moves most bf16
#: weights by less than half an ulp, so the losses could not show an update
#: gone wrong.
WARMUP = 0
PROMPTS, PROMPT_LEN, NEW_TOKENS = 4, 128, 16

#: A randomly initialised LM predicts near-uniformly over its vocabulary, so
#: its first loss sits near ln(vocab) (11.93 for 151936).
INIT_LOSS_TOL = 0.5
#: Decode-path vs full-forward logits, as a fraction of the largest |logit|.
#: bf16 keeps 8 significant bits (unit roundoff 2^-9).  The two paths round
#: the residual stream at different points in each layer (cached K/V against
#: recomputed K/V, one query row against blocked attention), so they part by
#: a few roundings per layer; 2^-5 allows 16 roundoffs of the largest logit.
LOGIT_RTOL = 2.0 ** -5
#: Sharded vs one-chip, per step.  The two meshes reduce the same sums in a
#: different order: with no update applied (learning rate 0) their f32
#: losses parted by at most 6.0e-5 on the chip.  One Adam step at 3e-4 moves
#: the loss by far more than these limits, so a mesh that drops or misreduces
#: the update fails them (tests/test_chip_smoke.py shows it at smoke size).
LOSS_ATOL = 1e-3
GRAD_NORM_RTOL = 1e-2


class CheckFailed(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """The first ``n_chips`` TPU devices; exits when JAX found no TPU."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX's first device is on platform "
            f"{d0.platform!r} ({d0.device_kind}); there is no CPU fallback")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:n_chips]


def watch_compiles() -> dict:
    """Running totals of backend compile seconds and persistent-cache hits
    (a cache hit is counted in the seconds as its retrieval time)."""
    import jax

    totals = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return totals


def model_config():
    from repro.configs import get_config

    return get_config(ARCH, num_layers=DEPTH, layer_kinds=("dense",) * DEPTH,
                      n_chunks=DEPTH)


def train_loop_config(**overrides):
    from repro.runtime.train_loop import TrainLoopConfig

    kw = dict(steps=STEPS, global_batch=GLOBAL_BATCH, seq_len=SEQ_LEN,
              policy=POLICY, warmup=WARMUP, log_every=1)
    kw.update(overrides)
    return TrainLoopConfig(**kw)


def memory_line(device) -> str:
    stats = device.memory_stats()
    if not stats:
        return f"[memory] {device}: not reported by this backend"
    peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
    return (f"[memory] {device}: peak_bytes_in_use {peak} / bytes_limit "
            f"{limit} ({peak / limit:.1%})")


def train_phase(cfg, mesh, loop, tag: str = "train") -> dict:
    """``run_training`` on ``mesh``; checks that every loss is finite and the
    first is near ln(vocab).  Returns ``run_training``'s result."""
    from repro.runtime.train_loop import run_training

    log(f"[{tag}] {cfg.name}: {cfg.total_params() / 1e9:.3f} B params, "
        f"mesh {dict(mesh.shape)}, batch {loop.global_batch} x seq "
        f"{loop.seq_len}, {loop.steps} steps, policy {loop.policy}")
    out = run_training(cfg, loop, mesh=mesh, log_fn=log)
    if out["plan"] is not None:
        log(f"[{tag}] plan:\n{out['plan'].summary()}")
    losses, secs = out["losses"], out["step_seconds"]
    for i, (loss, s) in enumerate(zip(losses, secs)):
        log(f"[{tag}] step {i}: loss {loss:.6f}, {s:.3f} s "
            f"(device-synchronised{', includes compile' if i == 0 else ''})")
    check(len(losses) == loop.steps,
          f"{len(losses)} of {loop.steps} steps ran")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    ln_v = math.log(cfg.vocab_size)
    check(abs(losses[0] - ln_v) <= INIT_LOSS_TOL,
          f"step-0 loss {losses[0]:.4f} is not within {INIT_LOSS_TOL} of "
          f"ln(vocab) = {ln_v:.4f}")
    for d in mesh.devices.flat:
        log(memory_line(d))
    return out


def _max_rel_err(got, ref) -> tuple:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    return err, scale


def decode_phase(cfg, params, *, batch: int = PROMPTS,
                 prompt_len: int = PROMPT_LEN, new_tokens: int = NEW_TOKENS,
                 seed: int = 0) -> dict:
    """``run_serving`` on seeded prompts, then the decode path's logits
    (prefill's last position and the first decode step) against the full
    forward pass over the same tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.lm import StagedLM
    from repro.runtime.serve_loop import ServeLoopConfig, run_serving, serve_fns

    model = StagedLM(cfg)
    prompts = (np.random.default_rng(seed)
               .integers(0, cfg.vocab_size, (batch, prompt_len))
               .astype(np.int32))
    loop = ServeLoopConfig(max_new_tokens=new_tokens,
                           max_len=prompt_len + new_tokens)
    # the first call compiles prefill and decode; the second runs them warm
    for when in ("cold, includes compile", "warm"):
        out = run_serving(cfg, params, prompts, loop, model=model)
        log(f"[decode] {batch} prompts x {prompt_len} tokens, {new_tokens} "
            f"new ({when}): prefill {out['prefill_s'] * 1e3:.1f} ms, decode "
            f"{out['decode_tokens_per_s']:.1f} tok/s (smoke numbers, not "
            f"metrics)")
    gen = out["generations"]
    check(gen.shape == (batch, new_tokens),
          f"generations shape {gen.shape} != {(batch, new_tokens)}")

    # the same jitted prefill/decode run_serving used, replayed for logits
    prefill, decode = serve_fns(model, loop.max_len)
    logits_p, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    first = jnp.asarray(gen[:, :1])
    logits_d, _ = decode(params, cache, first)
    seq = jnp.concatenate([jnp.asarray(prompts), first], axis=1)
    ref = jax.jit(model.forward_logits)(params, {"tokens": seq})
    errs = {}
    for name, got, want in (
            ("prefill", logits_p[:, -1], ref[:, prompt_len - 1]),
            ("first decode step", logits_d[:, -1], ref[:, prompt_len])):
        err, scale = _max_rel_err(got, want)
        errs[name] = err / scale
        log(f"[decode] {name} vs full forward: max |dlogit| {err:.5f}, "
            f"max |logit| {scale:.5f}, ratio {err / scale:.5f} "
            f"(limit {LOGIT_RTOL:.5f})")
        check(err <= LOGIT_RTOL * scale,
              f"{name} logits differ from the full forward pass by "
              f"{err:.5f} > {LOGIT_RTOL} x {scale:.5f}")
    out["logit_rel_err"] = errs
    return out


def one_chip(devices) -> None:
    from repro.launch.mesh import make_mesh

    cfg = model_config()
    mesh = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    out = train_phase(cfg, mesh, train_loop_config())
    params = out.pop("params")
    del out  # frees the optimizer state before decoding
    decode_phase(cfg, params)
    log(memory_line(devices[0]))


def param_bytes_per_device(params) -> dict:
    import jax

    per = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per[shard.device] = per.get(shard.device, 0) + shard.data.nbytes
    return per


def sharded(devices, cfg=None, loop=None) -> None:
    """The same training on a (data=2, model=2) mesh of ``devices[:4]`` and
    on a mesh of ``devices[:1]``: parameters must spread over the four
    devices and the per-step losses and gradient norms must agree."""
    import jax

    from repro.launch.mesh import make_mesh

    cfg = cfg or model_config()
    loop = loop or train_loop_config()
    mesh4 = make_mesh((2, 2), ("data", "model"), devices=devices[:4])
    out = train_phase(cfg, mesh4, loop, tag="train 2x2")
    params = out["params"]
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    per = param_bytes_per_device(params)
    for d in devices[:4]:
        log(f"[train 2x2] params on {d}: {per.get(d, 0)} of {total} bytes")
    check(set(per) == set(devices[:4]),
          f"parameters live on {sorted(map(str, per))}, not on all 4 devices")
    check(max(per.values()) <= total / 2,
          f"parameters are not sharded: one device holds "
          f"{max(per.values())} of {total} bytes")
    steps4 = list(zip(out["losses"], out["grad_norms"]))
    del out, params

    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    out1 = train_phase(cfg, mesh1, loop, tag="train 1x1")
    steps1 = list(zip(out1["losses"], out1["grad_norms"]))
    del out1
    bad = []
    for i, ((l4, g4), (l1, g1)) in enumerate(zip(steps4, steps1)):
        dl, dg = abs(l4 - l1), abs(g4 - g1) / g1
        log(f"[sharded] step {i}: loss 2x2 {l4:.6f} 1x1 {l1:.6f} |diff| "
            f"{dl:.6f} (limit {LOSS_ATOL}); grad norm 2x2 {g4:.6f} 1x1 "
            f"{g1:.6f} rel diff {dg:.6f} (limit {GRAD_NORM_RTOL})")
        if dl > LOSS_ATOL or dg > GRAD_NORM_RTOL:
            bad.append(i)
    check(not bad, f"sharded and one-chip training part at steps {bad}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and decode on one chip; 4: the sharded "
                         "training path against one chip, nothing else")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] persistent compile cache: {enable_compile_cache()}")
    compiles = watch_compiles()
    if args.chips == 4:
        sharded(devices)
    else:
        one_chip(devices)
    log(f"[compile] {compiles['compile_s']:.1f} s in backend compiles, "
        f"{compiles['cache_hits']} persistent-cache hits")
    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
