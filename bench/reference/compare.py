"""The numbers that decide ``correct``, each computed the same way for the
program, the control and the planted faults."""

from __future__ import annotations

import numpy as np

#: A leaf whose first reference gradient is below this share of the median
#: leaf's moves under Adam by round-off alone (a key's bias under softmax):
#: it is left out of the parameter change.
NOUGHT_GRAD = 1e-3


def leaf_gap(prog, ref, keep=None) -> tuple:
    """Worst leaf of ``|prog - ref| / max(ref, median ref)`` over the kept
    leaves; returns ``(gap, index)``.  Norms are compared, not their
    difference, each against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    keep = np.ones(len(ref), bool) if keep is None else np.asarray(keep)
    med = float(np.median(ref[keep]))
    gaps = np.abs(prog - ref) / np.maximum(ref, med)
    gaps = np.where(keep, gaps, -1.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def moved_leaves(ref_grad0) -> np.ndarray:
    """Leaves whose change counts: those the reference's gradient moves."""
    g = np.asarray(ref_grad0, np.float64)
    return g >= NOUGHT_GRAD * float(np.median(g))


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` each hold ``losses`` (the checked steps),
    ``grad0`` (per-leaf norms of the first gradient before clipping) and
    ``delta`` (per-leaf norms of the change after the checked steps)."""
    n = len(ref["losses"])
    loss = max(abs(float(a) - float(b))
               for a, b in zip(prog["losses"][:n], ref["losses"]))
    grad, gi = leaf_gap(prog["grad0"], ref["grad0"])
    upd, ui = leaf_gap(prog["delta"], ref["delta"],
                       keep=moved_leaves(ref["grad0"]))
    paths = ref.get("paths")
    where = {}
    if paths is not None:
        where = {"grad": paths[gi], "update": paths[ui]}
    return {"loss": loss, "grad": grad, "update": upd, "where": where}


def logit_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at that position.  ``ref_logits``: (n, V) float32
    rows, ``tokens``: (n,) ids."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return float(np.max(best - got))
