"""Production mesh builders.

Functions (not module constants) so importing never touches jax device
state — `dryrun.py` must set XLA_FLAGS before any jax initialization.

Every axis is ``Auto``: the programs place data with ``shard_map`` specs
and sharding constraints, and leave the rest to the compiler (the
installed JAX would otherwise default to ``Explicit`` axes, under which
sharding mismatches become type errors).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, *, pod: int = 0):
    """Small meshes: CPU tests (device count permitting) and one host's
    chips."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
