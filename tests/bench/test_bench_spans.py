"""The program's spans as the benchmark reads them: a tiny training run
under the profiler, read back by ``bench.trace.extract``, and the readers
of the five metrics over spans and gauges (``train_loop.host_gap_ms``,
``data.batch_ms``, ``plan.setup_s``, ``plan.step_time_error_pct``,
``plan.memory_error_pct``)."""

import glob
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import tiny_cells  # noqa: E402
from bench import trace  # noqa: E402
from bench.run import _metric_reader, model_config  # noqa: E402

MS = 1e6  # nanoseconds

# A window of 1000 ms.  Step 0 dispatches at [60, 80] and syncs at
# [80, 100]; steps 1-3 dispatch after a sync, their gaps 130-100 = 30,
# 350-300 = 50 and 540-500 = 40 ms (median 40); step 3's sync ends after
# the window and is not read.  Two batches lie inside the window (20 and
# 25 ms, median 22.5); one starts before it and one ends after it.
HAND = {
    "devices": {"/device:TPU:0": [[100 * MS, 200 * MS, "fusion"]]},
    "modules": {},
    "host": [[0, 1000 * MS, "bench.window"],
             [60 * MS, 20 * MS, "train.dispatch"],
             [80 * MS, 20 * MS, "train.sync"],
             [110 * MS, 5 * MS, "train.data_wait"],
             [120 * MS, 10 * MS, "train.dispatch"],
             [130 * MS, 170 * MS, "train.sync"],
             [310 * MS, 40 * MS, "train.dispatch"],
             [350 * MS, 150 * MS, "train.sync"],
             [520 * MS, 20 * MS, "train.dispatch"],
             [540 * MS, 560 * MS, "train.sync"],
             [-10 * MS, 15 * MS, "data.batch"],
             [200 * MS, 20 * MS, "data.batch"],
             [400 * MS, 25 * MS, "data.batch"],
             [990 * MS, 20 * MS, "data.batch"]],
}

GAUGES = {"plan.chain_s": 1.5, "plan.solve_s": 0.25,
          "plan.predicted_step_s": 0.2, "plan.planned_bytes": 860.0,
          "train.step_bytes": 1000.0}


@pytest.fixture
def gauges():
    from repro.obs import metrics

    metrics.reset()
    for name, v in GAUGES.items():
        metrics.gauge(name).set(v)
    yield
    metrics.reset()


def _run():
    return {"trace_events": HAND, "trace": {"busy_s": 0.8},
            "attempted": 2}


@pytest.mark.parametrize("name, expected", [
    ("train_loop.host_gap_ms", 40.0),
    ("data.batch_ms", 22.5),
    ("plan.setup_s", 1.75),
    ("plan.step_time_error_pct", 50.0),   # 0.8 s busy / 2 steps vs 0.2 s
    ("plan.memory_error_pct", 14.0),      # |1000 - 860| / 1000
])
def test_reader_by_hand(gauges, name, expected):
    assert _metric_reader(name)(_run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "train_loop.host_gap_ms", "data.batch_ms", "plan.setup_s",
    "plan.step_time_error_pct", "plan.memory_error_pct"])
def test_reader_reads_none_without_its_spans_or_gauges(name):
    from repro.obs import metrics

    metrics.reset()
    read = _metric_reader(name)
    # a program that names no spans and sets no gauges
    bare = {"trace_events": {"devices": HAND["devices"], "modules": {},
                             "host": [[0, 1000 * MS, "bench.window"]]},
            "trace": {"busy_s": 0.8}, "attempted": 2}
    assert read(bare) is None
    assert read({}) is None


def test_memory_error_needs_both_gauges(gauges):
    from repro.obs import metrics

    metrics.reset()
    metrics.gauge("plan.planned_bytes").set(860.0)
    assert _metric_reader("plan.memory_error_pct")(_run()) is None


def test_tiny_run_spans_read_back_from_the_profiler(tmp_path, monkeypatch):
    """Three steps of the tiny cell's model under ``jax.profiler.trace``:
    each ``train.*`` span once per step, in loop order inside its
    ``train.step``; one ``data.batch`` per batch the prefetch thread made;
    the planner's two spans and the step's compile once each."""
    import jax

    from repro.data.pipeline import SyntheticLMData
    from repro.runtime.train_loop import TrainLoopConfig, run_training

    made = []
    batch_at = SyntheticLMData.batch_at
    monkeypatch.setattr(SyntheticLMData, "batch_at",
                        lambda self, s: (made.append(s), batch_at(self, s))[1])
    steps = 3
    loop = TrainLoopConfig(steps=steps, global_batch=4, seq_len=32, seed=5,
                           policy="rotor:auto", log_every=100)
    with jax.profiler.trace(str(tmp_path)):
        run_training(model_config(tiny_cells.MODEL), loop,
                     log_fn=lambda _: None)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = trace.extract(path)["host"]

    def named(name):
        return sorted((s, s + d) for s, d, n in host if n == name)

    for once in ("plan.chain", "plan.solve", "train.compile"):
        assert len(named(once)) == 1, once
    assert named("plan.chain")[0][1] <= named("plan.solve")[0][0]
    order = ("train.data_wait", "train.device_put", "train.dispatch",
             "train.sync")
    per_step = [named(n) for n in order]
    outer = named("train.step")
    assert len(outer) == steps
    assert all(len(sp) == steps for sp in per_step)
    for k in range(steps):
        starts = [sp[k][0] for sp in per_step]
        assert starts == sorted(starts)
        assert outer[k][0] <= starts[0] and per_step[-1][k][1] <= outer[k][1]
    assert len(made) == len(set(made)) >= steps
    assert len(named("data.batch")) == len(made)
