"""Seconds the planner spent: profiling the chain plus solving it (the
program's gauges ``plan.chain_s`` and ``plan.solve_s``)."""

from bench.metrics._spans import gauge


def read(run: dict):
    chain, solve = gauge("plan.chain_s"), gauge("plan.solve_s")
    return None if chain is None or solve is None else chain + solve
