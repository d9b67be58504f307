"""How far the plan's predicted step time (the gauge
``plan.predicted_step_s``) lies from the device's busy time per step in
the traced window, as a share of the latter."""

from bench.metrics._spans import gauge, relative_error_pct


def read(run: dict):
    red, steps = run.get("trace"), run.get("attempted")
    if red is None or not steps:
        return None
    return relative_error_pct(red["busy_s"] / steps,
                              gauge("plan.predicted_step_s"))
