"""The training traffic: seeded synthetic token batches.

A copy of the rule of ``SyntheticLMData.batch_at`` (``data/pipeline.py``),
which ``run_training`` draws its batches from, so that the reference
trains on the same rows without importing the program.  Each step draws a
Markov rule ``next = (tok * a + 1) mod V`` with ``a`` in 2..6 and replaces
10 % of the tokens with uniform noise; rows and steps all differ.
"""

from __future__ import annotations

import numpy as np


def batch_at(vocab: int, batch: int, seq: int, seed: int, step: int,
             host: int = 0) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([seed, host, step]))
    a = rng.integers(2, 7)
    toks = np.empty((batch, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, batch)
    noise = rng.random((batch, seq)) < 0.1
    rand = rng.integers(0, vocab, (batch, seq))
    for t in range(seq):
        nxt = (toks[:, t] * a + 1) % vocab
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
            "loss_mask": np.ones((batch, seq), np.float32)}
