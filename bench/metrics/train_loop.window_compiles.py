"""Backend compiles that ended inside the measured window (should be 0)."""


def read(run: dict):
    return run.get("window_compiles")
