"""The repo's one mesh constructor, plus the production layouts.

Functions (not module-level constants) so importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips (data × model).
Multi-pod: 2×16×16 = 512 chips with a leading "pod" axis — the pod axis is
pure data parallelism whose gradient all-reduce crosses the (slow) inter-pod
links; the dry-run proves it shards.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """A mesh with Auto axis types.  The model code shards with
    ``with_sharding_constraint`` and plain gathers, which Explicit axes
    (``jax.make_mesh``'s default since JAX 0.9) reject."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape), **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))
