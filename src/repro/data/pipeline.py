"""Deterministic synthetic LM data pipeline.

Production posture: per-host sharded generation (each host materializes only
its slice of the global batch), deterministic per (seed, step) so that a
checkpoint-restart resumes the *exact* stream — a fault-tolerance requirement
(the restarted run must consume the same data as the lost one).  A background
thread prefetches ``prefetch`` batches ahead; each batch it makes is one
``data.batch`` span (:func:`repro.obs.trace.span`) in a profiler trace.
"""

from __future__ import annotations

import queue
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import span


def make_batch_specs(cfg, batch: int, seq: int) -> Dict[str, jax.ShapeDtypeStruct]:
    if cfg.modality == "text":
        return {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32)}
    if cfg.modality == "audio_embed":
        return {"embeds": jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.dtype),
                "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
                "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32)}
    P = cfg.prefix_len
    return {"image_embeds": jax.ShapeDtypeStruct((batch, P, cfg.d_model), cfg.dtype),
            "tokens": jax.ShapeDtypeStruct((batch, seq - P), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch, seq - P), jnp.int32),
            "loss_mask": jax.ShapeDtypeStruct((batch, seq - P), jnp.float32)}


class SyntheticLMData:
    """Markov-ish synthetic token stream (structured enough that loss drops)."""

    def __init__(self, cfg, global_batch: int, seq_len: int, seed: int = 0,
                 host_index: int = 0, host_count: int = 1, prefetch: int = 2):
        assert global_batch % host_count == 0
        self.cfg = cfg
        self.local_batch = global_batch // host_count
        self.seq = seq_len
        self.seed = seed
        self.host = host_index
        self._step = 0
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self.prefetch = prefetch

    # -- deterministic batch synthesis -------------------------------------

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        cfg = self.cfg
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.host, step]))
        B, S, V = self.local_batch, self.seq, cfg.vocab_size
        # tokens with local structure: next token = (tok*a + b) % V w/ noise
        a = rng.integers(2, 7)
        toks = np.empty((B, S + 1), np.int64)
        toks[:, 0] = rng.integers(0, V, B)
        noise = rng.random((B, S)) < 0.1
        rand = rng.integers(0, V, (B, S))
        for t in range(S):
            nxt = (toks[:, t] * a + 1) % V
            toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
        tokens = toks[:, :-1].astype(np.int32)
        labels = toks[:, 1:].astype(np.int32)
        mask = np.ones((B, S), np.float32)
        if cfg.modality == "text":
            return {"tokens": tokens, "labels": labels, "loss_mask": mask}
        if cfg.modality == "audio_embed":
            emb = rng.standard_normal((B, S, cfg.d_model)).astype(np.float32)
            return {"embeds": emb, "labels": labels, "loss_mask": mask}
        P = cfg.prefix_len
        img = rng.standard_normal((B, P, cfg.d_model)).astype(np.float32)
        return {"image_embeds": img, "tokens": tokens[:, :S - P],
                "labels": labels[:, :S - P],
                "loss_mask": mask[:, :S - P]}

    # -- iteration with prefetch -------------------------------------------

    def start(self, from_step: int = 0) -> None:
        self._step = from_step
        self._q = queue.Queue(maxsize=self.prefetch)
        self._stop = threading.Event()

        def worker():
            s, b = from_step, None
            while not self._stop.is_set():
                if b is None:  # a batch the full queue refused is kept
                    with span("data.batch", step=s):
                        b = self.batch_at(s)
                try:
                    self._q.put(b, timeout=0.5)
                except queue.Full:
                    continue
                s, b = s + 1, None

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def next(self) -> Dict[str, np.ndarray]:
        if self._q is None:
            b = self.batch_at(self._step)
            self._step += 1
            return b
        return self._q.get()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(timeout=2.0)
            self._thread = None
            self._q = None
