import os

# Tests must see 1 CPU device (the dry-run sets its own 512-device flag in a
# subprocess); also keep XLA quiet and deterministic.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Keep the solver cache memory-only during tests: a fresh process state per
# run, no reads from (or writes to) the developer's ~/.cache — otherwise a
# broken DP fill could go green against Solutions cached by an earlier run.
# Cache tests point REPRO_SOLVER_CACHE_DIR at a tmpdir explicitly.
os.environ.setdefault("REPRO_SOLVER_CACHE_DIR", "")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _pallas_dp_fill_interpreted():
    """The Pallas DP fills compile only for a TPU: the tests that plan with
    ``impl="pallas"`` / ``"pallas_fused"`` run them in interpret mode."""
    from repro.kernels.dp_fill import ops

    ops.set_interpret(True)
    yield
    ops.set_interpret(False)
