"""Seconds of backend compiles in the run (persistent-cache hits count as
their retrieval time), from JAX's monitoring events."""


def read(run: dict):
    return run.get("compile_s")
