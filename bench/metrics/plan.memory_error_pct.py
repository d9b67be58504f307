"""How far the planner's picture of the step's memory on one device (the
gauge ``plan.planned_bytes``: activation peak plus weights, gradients and
Adam moments) lies from the compiled step's (``train.step_bytes``, from
``memory_analysis``), as a share of the latter."""

from bench.metrics._spans import gauge, relative_error_pct


def read(run: dict):
    return relative_error_pct(gauge("train.step_bytes"),
                              gauge("plan.planned_bytes"))
