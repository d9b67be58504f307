"""Idle share of the device over a traced window of training steps."""

from bench.metrics._common import idle_percent


def read(run: dict):
    return idle_percent(run)
