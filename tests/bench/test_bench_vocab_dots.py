"""The reader of ``train.vocab_dots``: the program's gauge of the compiled
step's vocabulary-wide matmuls, as the benchmark reads it."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.run import _metric_reader  # noqa: E402


@pytest.fixture
def registry():
    from repro.obs import metrics

    metrics.reset()
    yield metrics
    metrics.reset()


@pytest.mark.parametrize("value", [2.0, 3.0])
def test_reader_reads_the_gauge(registry, value):
    registry.gauge("train.vocab_dots").set(value)
    run = {"trace": {"busy_s": 0.8}, "attempted": 2}
    assert _metric_reader("train.vocab_dots")(run) == pytest.approx(value)


def test_reader_reads_none_without_the_gauge(registry):
    read = _metric_reader("train.vocab_dots")
    # a program that sets no such gauge, as before the gauge existed
    registry.gauge("train.step_bytes").set(1000.0)
    assert read({"trace": {"busy_s": 0.8}, "attempted": 2}) is None
    assert read({}) is None
