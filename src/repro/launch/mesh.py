"""Mesh construction — the one place a ``jax.sharding.Mesh`` is built.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A mesh whose axes are all ``Auto``.  The logical-axis rules
    (:mod:`repro.distributed.sharding`) constrain activations with GSPMD
    ``with_sharding_constraint``, which only accepts ``Auto`` axes;
    ``jax.make_mesh`` now defaults to ``Explicit`` ones."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
