"""Production mesh builders (pure functions — importing never touches jax
device state; the dry-run sets XLA_FLAGS before any jax import).

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to Explicit
axes, under which the ``with_sharding_constraint`` calls of
``parallel/sharding.py`` raise."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    return _mesh(shape, axes)


def make_local_mesh(axes=("data", "model")):
    """1-device mesh with production axis names (CPU tests/smokes)."""
    return _mesh((1,) * len(axes), axes)


def make_pipeline_mesh(n_stages: int = 4):
    return _mesh((n_stages,), ("stage",))
