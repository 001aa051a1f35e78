"""Production mesh construction (brief: MULTI-POD DRY-RUN step 1).

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. Single pod: 16x16 ("data", "model") = 256 chips; multi-pod:
2x16x16 ("pod", "data", "model") = 512 chips. The "pod" axis folds into data
parallelism (BATCH_AXES) everywhere.

Every mesh is built with Auto axes: ``sharding.rules`` places arrays with
``with_sharding_constraint`` and lets GSPMD propagate, which Explicit axes
(``jax.make_mesh``'s default since JAX 0.7) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over the real local devices (tests, smoke runs, one TPU host)."""
    n = len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))
