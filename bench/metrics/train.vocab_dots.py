"""Matmuls in the compiled training step whose result has a
vocabulary-sized dimension (the program's gauge ``train.vocab_dots``): the
head's logits and weight gradient, and any recompute of the logits."""

from bench.metrics._spans import gauge


def read(run: dict):
    return gauge("train.vocab_dots")
