"""Batched serving loop: prefill a batch of prompts, then greedy-decode with
a jitted one-token step (continuous-batching-lite: finished sequences keep
decoding into padding; a real deployment would swap in new requests — the
slot bookkeeping below is where that plugs in).

KV-cache residency is pluggable: pass a :func:`repro.plan.plan_serving` plan
(``plan=``) to stage the planner's cold-layer set through the pinned host
pool around every step, or ``kv_policy="lru"`` with a byte budget for the
naive on-demand baseline the planner is benchmarked against
(:mod:`repro.runtime.kv_residency`).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.lm import StagedLM
from ..obs import metrics as obs_metrics


@dataclasses.dataclass
class ServeLoopConfig:
    max_new_tokens: int = 16
    max_len: int = 256
    greedy: bool = True
    eos_id: Optional[int] = None


def serve_fns(model, max_len: int):
    """Jitted prefill/decode pair, memoized per (model instance, max_len) so
    repeated `run_serving` calls (benchmark sweeps) don't retrace."""
    memo = model.__dict__.setdefault("_serve_jit", {})
    fns = memo.get(max_len)
    if fns is None:
        prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
        decode = jax.jit(model.decode_step, donate_argnums=(1,))
        fns = memo[max_len] = (prefill, decode)
    return fns


def _make_residency(model, layout, tracer, *, plan, kv_policy, kv_budget,
                    host, host_buffer):
    """Resolve the KV-residency policy for this serving run (None = keep the
    whole cache in device memory)."""
    if plan is not None and kv_policy is not None:
        raise ValueError("pass either plan= or kv_policy=, not both")
    if plan is None and kv_policy is None:
        return None
    from ..offload.host_buffer import HostBuffer
    from .kv_residency import LRUKV, PlannedKV
    buffer = host_buffer if host_buffer is not None else HostBuffer(None)
    if plan is not None:
        from ..plan.serving import kv_residency_layers
        plan._verify_or_raise("refusing to serve an unverified kv plan")
        layers = kv_residency_layers(plan, budget_bytes=kv_budget)
        link = host or (plan.chain.host if plan.chain is not None else None)
        return PlannedKV(model, layout, layers, link=link, buffer=buffer,
                         tracer=tracer)
    if kv_policy != "lru":
        raise ValueError(f"unknown kv_policy {kv_policy!r}; expected 'lru' "
                         f"(or pass plan= for the planned policy)")
    if kv_budget is None:
        raise ValueError("kv_policy='lru' needs kv_budget= (device KV bytes)")
    return LRUKV(model, layout, kv_budget, link=host, buffer=buffer,
                 tracer=tracer)


def run_serving(cfg, params, prompts: np.ndarray, loop: ServeLoopConfig,
                model: Optional[StagedLM] = None, tracer=None, *,
                plan=None, kv_policy: Optional[str] = None,
                kv_budget: Optional[float] = None, host=None,
                host_buffer=None) -> Dict[str, Any]:
    """prompts: (B, S0) int32 token batch. Returns generations + stats.

    ``tracer`` (a :class:`repro.obs.trace.Tracer`, opt-in) records one
    ``Decode`` span per emitted token plus a ``Step`` span for the prefill;
    each span's ``bytes`` field carries the *logical* KV residency at that
    point — ``CacheLayout.logical_bytes(pos)``, i.e. what the cache holds,
    not the padded ``max_len`` allocation.  Gauges: ``serve.kv_bytes``
    (logical, tracks ``pos``) and ``serve.kv_bytes_allocated`` (the padded
    allocation, constant per run).  ``serve.decode_tokens`` counts only live
    tokens — sequences finished by ``eos_id`` stop contributing even while
    they keep decoding into padding.

    KV residency: ``plan=`` (a verified :func:`repro.plan.plan_serving`
    plan; ``kv_budget=`` optionally re-clamps to the requested budget when
    the plan fell back to min-memory) or ``kv_policy="lru"`` +
    ``kv_budget=``.  ``host`` overrides the
    :class:`~repro.core.chain.HostTransferModel`; ``host_buffer`` supplies
    the pinned pool (default: unbounded accounting-only pool).
    """
    model = model or StagedLM(cfg)
    B, S0 = prompts.shape
    if S0 + loop.max_new_tokens > loop.max_len:
        raise ValueError(
            f"prompt length {S0} + max_new_tokens {loop.max_new_tokens} "
            f"exceeds max_len {loop.max_len}; raise ServeLoopConfig.max_len")
    rec = tracer is not None and getattr(tracer, "enabled", True)
    layout = model.cache_layout(B, loop.max_len)
    residency = _make_residency(model, layout, tracer, plan=plan,
                                kv_policy=kv_policy, kv_budget=kv_budget,
                                host=host, host_buffer=host_buffer)

    prefill, decode = serve_fns(model, loop.max_len)

    ts0 = tracer.now() if rec else 0.0
    t0 = time.perf_counter()
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
    next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    jax.block_until_ready(next_tok)
    t_prefill = time.perf_counter() - t0
    pos0 = int(cache["pos"])
    kv_bytes = layout.logical_bytes(pos0)
    obs_metrics.gauge("serve.kv_bytes").set(float(kv_bytes))
    obs_metrics.gauge("serve.kv_bytes_allocated").set(
        float(layout.allocated_bytes))
    obs_metrics.histogram("serve.prefill_seconds").observe(t_prefill)
    if rec:
        tracer.record("Step", 0, ts0, tracer.now(), bytes=kv_bytes)

    if residency is not None:
        cache = residency.stage_initial(cache)

    out_tokens: List[np.ndarray] = [np.asarray(next_tok)]
    done = np.zeros((B,), bool)
    if loop.eos_id is not None:
        done |= out_tokens[0] == loop.eos_id
    decode_tokens = 0
    t0 = time.perf_counter()
    for tok_idx in range(loop.max_new_tokens - 1):
        if residency is not None:
            cache = residency.begin_step(cache)
        td0 = tracer.now() if rec else 0.0
        ts = time.perf_counter()
        logits, cache = decode(params, cache, next_tok[:, None])
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = np.asarray(next_tok)
        step_wall = time.perf_counter() - ts
        kv_bytes = layout.logical_bytes(pos0 + tok_idx + 1)
        obs_metrics.gauge("serve.kv_bytes").set(float(kv_bytes))
        if rec:
            tracer.record("Decode", tok_idx + 1, td0, tracer.now(),
                          bytes=kv_bytes)
        decode_tokens += int((~done).sum())
        if loop.eos_id is not None:
            done |= toks == loop.eos_id
        out_tokens.append(toks)
        finished = loop.eos_id is not None and bool(done.all())
        last = finished or tok_idx == loop.max_new_tokens - 2
        if residency is not None and not last:
            # no step follows the last one — nothing to stage back for
            cache = residency.end_step(cache, step_wall)
        if finished:
            break
    jax.block_until_ready(next_tok)
    t_decode = time.perf_counter() - t0
    gen = np.stack(out_tokens, axis=1)
    obs_metrics.counter("serve.decode_tokens").inc(decode_tokens)
    out = {
        "generations": gen,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tokens": decode_tokens,
        "decode_tokens_per_s": decode_tokens / max(t_decode, 1e-9),
        "kv_bytes": kv_bytes,
        "kv_bytes_allocated": layout.allocated_bytes,
    }
    if residency is not None:
        out.update(residency.result_stats())
    return out
