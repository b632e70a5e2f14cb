"""The repo's one mesh constructor.

A function (not a module-level constant) so importing this module never
touches jax device state.
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
