"""Median time to make one batch: the ``data.batch`` spans of the data
pipeline's prefetch thread inside the traced window."""

from bench.metrics._spans import median_ms, spans


def read(run: dict):
    return median_ms([e - s for s, e in spans(run, "data.batch")])
