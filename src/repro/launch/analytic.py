"""Analytic roofline terms — the primary §Roofline numbers.

Why analytic: XLA-CPU's ``HloCostAnalysis`` counts ``while``-loop bodies
ONCE (measured: an 8-iteration scan of d=256 matmuls reports 1.19 MFLOP vs
4.19 MFLOP true — see EXPERIMENTS.md §Caveats), so for scan-based models the
compiled FLOPs/bytes/collectives are under-counted by ~layers-per-chunk.
The cost model below is exact under the paper's execution model:

- **compute**: per-stage analytic FLOPs (``models/flops.py``, 2·N·M·K math)
  × the *schedule's* per-stage execution counts (recompute included — this
  is where rotor's time-for-memory trade shows up), ÷ chips ÷ peak.
- **memory**: per-device HBM traffic = activation stream (each forward op
  reads ``ω_a``/writes its output, each backward reads ``ā`` + writes δ and
  parameter gradients) + per-execution parameter reads (post-all-gather TP
  shard) + optimizer state read/write; decode adds the KV/SSM cache read.
- **collective**: FSDP all-gathers (param shard × executions), gradient
  reduce-scatter + cross-pod all-reduce, MoE all-to-alls (dispatch buffer ×
  2 directions × executions), and the logits-reduction for vocab-sharded
  heads.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import numpy as np

from ..core.schedule import BWD, F_ALL, F_CK, F_NONE, Schedule
from ..models.flops import _layer_flops, stage_flops
from ..core.devices import V5E


def _bytes_of_tree(tree) -> int:
    return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def _axis(mesh, name) -> int:
    return mesh.shape.get(name, 1)


def train_terms(cfg, shape, mesh, model, chain, schedule: Optional[Schedule]
                ) -> Dict[str, float]:
    B, S = shape.global_batch, shape.seq_len
    n_dev = mesh.size
    dp = _axis(mesh, "pod") * _axis(mesh, "data")
    tp = _axis(mesh, "model")
    if B % dp:
        dp = 1
    fwd_flops, bwd_flops = stage_flops(cfg, B, S)
    sched = schedule or Schedule.store_all(chain.length)
    fwd_counts: Dict[int, int] = {}
    for kind, l in sched.ops:
        if kind in (F_ALL, F_CK, F_NONE):
            fwd_counts[l] = fwd_counts.get(l, 0) + 1

    # --- compute ---------------------------------------------------------
    total_flops = 0.0
    inner = 1.0 if cfg.scan_layer_remat in ("full", "save_moe") else 0.0
    for l in range(1, chain.length + 2):
        c = fwd_counts.get(l, 1)
        total_flops += c * fwd_flops[l - 1]
        # backward = 2×fwd (+1×fwd replay if inner per-layer remat)
        total_flops += (2.0 + inner) * fwd_flops[l - 1]
    compute_s = total_flops / n_dev / V5E.flops_bf16

    # --- memory traffic (per device) --------------------------------------
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    stage_specs = model.stage_params(params_spec)
    stage_pbytes = [_bytes_of_tree(s) for s in stage_specs]
    p_total = _bytes_of_tree(params_spec)
    traffic = 0.0
    for kind, l in sched.ops:
        pb = stage_pbytes[l - 1] / tp  # post-all-gather TP-local weights
        if kind in (F_ALL, F_CK, F_NONE):
            out = chain.wabar[l - 1] if kind == F_ALL else (
                chain.wa[l] if l <= chain.length else 0.0)
            traffic += chain.wa[l - 1] + out + pb
        else:  # backward: read ā + δ + params, write δ + param grads
            traffic += (chain.wabar[l - 1] + 2 * chain.wdelta[l - 1] + 2 * pb)
    # optimizer: p(read+write) bf16 + m,v f32 (read+write), grads read — all
    # fully sharded (ZeRO-3): 2·2 + 2·8 + 2 = 22 bytes/param ÷ n_dev
    traffic += 22.0 * (p_total / 2) / n_dev
    memory_s = traffic / V5E.hbm_bw

    # --- collectives (per device) ------------------------------------------
    coll = 0.0
    fsdp = dp
    for kind, l in sched.ops:
        shard = stage_pbytes[l - 1] / n_dev
        if fsdp > 1:
            coll += shard * (fsdp - 1)  # all-gather the FSDP dim per use
    # gradient reduce-scatter (ring: ~shard×(dp-1) per device) + pod reduce
    coll += (p_total / n_dev) * (fsdp - 1)
    # MoE all-to-alls: dispatch+return, fwd / bwd / inner-remat replay
    n_moe = sum(1 for k in cfg.layer_kinds if k == "moe")
    if n_moe and cfg.num_experts % tp == 0 and tp > 1:
        Tl = B * S // dp
        cap = -(-max(4, math.ceil(Tl * cfg.moe_top_k / cfg.num_experts
                                  * cfg.moe_capacity_factor)) // 8) * 8
        buf = cfg.num_experts * cap * cfg.d_model * 2  # bf16
        passes = 2 + 2 + (2 if cfg.scan_layer_remat == "full" else 0)
        coll += n_moe * buf * passes * (tp - 1) / tp
    collective_s = coll / V5E.ici_bw
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s,
            "flops_per_device": total_flops / n_dev,
            "hbm_bytes_per_device": traffic,
            "collective_bytes_per_device": coll}


def decode_terms(cfg, shape, mesh, model) -> Dict[str, float]:
    B, S = shape.global_batch, shape.seq_len
    n_dev = mesh.size
    dp = _axis(mesh, "pod") * _axis(mesh, "data")
    if B % dp:
        dp = 1
    tp = _axis(mesh, "model")
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_bytes = _bytes_of_tree(params_spec)
    cache_spec = jax.eval_shape(lambda: model.init_cache(B, S))
    c_bytes = _bytes_of_tree(cache_spec)
    # flops: one token through active params + attention over the cache
    flops = 2.0 * cfg.active_params() * B
    for kind, start, length in cfg.chunks:
        if kind in ("dense", "moe"):
            flops += length * (_layer_flops(cfg, "dense", B, 1, kv_len=S)
                               - _layer_flops(cfg, "dense", B, 1, kv_len=1))
    compute_s = flops / n_dev / V5E.flops_bf16
    # memory: read the resident param shard + the whole cache; the cache
    # write-back is only the new token's slice (the cache buffer is donated
    # and aliased in place on TPU)
    traffic = p_bytes / n_dev + c_bytes / n_dev * (1.0 + 1.0 / max(S, 1))
    memory_s = traffic / V5E.hbm_bw
    # collectives: per-layer activation all-reduce for TP (y partial sums)
    n_layers = cfg.num_layers
    coll = n_layers * B / dp * cfg.d_model * 2 * 2 * (tp - 1) / tp
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll / V5E.ici_bw,
            "flops_per_device": flops / n_dev,
            "hbm_bytes_per_device": traffic,
            "collective_bytes_per_device": coll}


def prefill_terms(cfg, shape, mesh, model) -> Dict[str, float]:
    B, S = shape.global_batch, shape.seq_len
    n_dev = mesh.size
    fwd_flops, _ = stage_flops(cfg, B, S)
    flops = float(sum(fwd_flops))
    params_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_bytes = _bytes_of_tree(params_spec)
    cache_spec = jax.eval_shape(lambda: model.init_cache(B, S))
    c_bytes = _bytes_of_tree(cache_spec)
    act = B * S * cfg.d_model * 2 * (2 * cfg.num_layers)  # stream in/out
    traffic = (p_bytes + act + c_bytes) / n_dev
    tp = _axis(mesh, "model")
    coll = (p_bytes / n_dev) * (mesh.size / tp - 1)  # FSDP gathers
    return {"compute_s": flops / n_dev / V5E.flops_bf16,
            "memory_s": traffic / V5E.hbm_bw,
            "collective_s": coll / V5E.ici_bw,
            "flops_per_device": flops / n_dev,
            "hbm_bytes_per_device": traffic,
            "collective_bytes_per_device": coll}
