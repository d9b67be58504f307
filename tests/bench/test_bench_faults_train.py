"""A training run with its timed path broken underneath comes out not
correct: the step that returns its state unchanged, and the step that
leaves half of the batch out of the loss (the mean taken over the rest)."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny_cells  # noqa: E402


def broken_step(fault):
    import repro.runtime.train_loop as tl

    real = tl.make_train_step

    def make(*args, **kw):
        step = real(*args, **kw)

        def bad(params, opt_state, batch, s):
            if fault == "half_batch":
                m = batch["loss_mask"]
                batch = dict(batch, loss_mask=m.at[m.shape[0] // 2:].set(0.0))
                return step(params, opt_state, batch, s)
            _, _, metrics = step(params, opt_state, batch, s)
            return params, opt_state, metrics

        return bad

    return make


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(monkeypatch, fault):
    import repro.runtime.train_loop as tl

    if fault is not None:
        monkeypatch.setattr(tl, "make_train_step", broken_step(fault))
    res = tiny_cells.run(tiny_cells.train())
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["loss", "grad", "update"]
