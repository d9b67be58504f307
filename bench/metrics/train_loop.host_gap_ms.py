"""The training loop's host time between steps: the median, over the
window's steps after its first, of the end of step k's ``train.dispatch``
span minus the end of step k−1's ``train.sync`` span.  The loop waits for
each step, so the chip has nothing queued in that time."""

from bench.metrics._spans import host_gaps_ns, median_ms


def read(run: dict):
    return median_ms(host_gaps_ns(run, "train.sync", "train.dispatch"))
