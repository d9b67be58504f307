"""Model FLOPs utilization of the training window: the FLOPs the forward
and backward passes require (causal attention, recompute not counted,
``bench/flops.py``) times tokens per second, over chips times peak."""

from bench.metrics._common import mfu_percent


def read(run: dict):
    return mfu_percent(run)
