"""Mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first init.

Every mesh here has Auto axes.  ``jax.make_mesh`` defaults to Explicit
axes, under which the sharding rules of ``parallel/`` are type errors
(e.g. the embedding gather of batch-sharded tokens from a table that is
also sharded over "data"); they are written for sharding propagation
plus ``with_sharding_constraint``.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """An Auto-axis mesh of ``shape`` over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh over the real local device (smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))
