"""Parameter descriptors: one definition drives init, sharding and shape
checking.

A model module builds a pytree of :class:`ParamDef` (shape + logical axes
+ initializer).  From that single tree we derive:

* ``init_params``  — materialized arrays (fp32 masters or bf16 serving
  weights),
* ``param_specs``  — `PartitionSpec` tree (TP rules + FSDP), via
  `repro.distributed.sharding.resolve_spec`,
* analytic parameter counts (cross-checked against `ModelConfig.param_count`).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import resolve_spec


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    laxes: Tuple[Optional[str], ...]
    init: str = "fan_in"     # fan_in | normal | zeros | ones | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.laxes), (self.shape, self.laxes)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64))


def is_def(x: Any) -> bool:
    return isinstance(x, ParamDef)


def stacked(defs: Any, n: int) -> Any:
    """Prepend a scan dim of length n to every ParamDef in a tree."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.laxes, d.init, d.scale),
        defs, is_leaf=is_def)


# axes a weight is applied independently along (scan layers, experts),
# and the model-width axes a projection reads or writes
_BATCH_AXES = ("layers", "experts")
_MODEL_AXES = ("embed", "dmodel_rp")


def fan_in(d: ParamDef) -> int:
    """Inputs summed into each output of the projection ``d``.

    A weight that reads the model width (``(d, heads, head_dim)``,
    ``(d, ff)``) sums over that one dim; one that writes it
    (``(heads, head_dim, d)``, ``(ff, d)``) sums over every dim before
    it, leading layer/expert axes aside.  Anything else (conv taps, the
    latent-attention up-projections) sums over its second-to-last dim.
    """
    if len(d.shape) < 2:
        return d.shape[0]
    lead = 0
    while d.laxes[lead] in _BATCH_AXES:
        lead += 1
    if d.laxes[lead] in _MODEL_AXES:
        return d.shape[lead]
    if d.laxes[-1] in _MODEL_AXES:
        return int(np.prod(d.shape[lead:-1]))
    return d.shape[-2]


def _materialize(d: ParamDef, key: jax.Array, dtype) -> jax.Array:
    """One leaf, drawn at ``dtype``: a bf16 model never passes through a
    float32 copy of any leaf."""
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "normal":
        std = 0.02 * d.scale
    elif d.init == "small":
        std = 0.006 * d.scale
    else:
        std = d.scale / np.sqrt(max(fan_in(d), 1))
    return jax.random.normal(key, d.shape, dtype) * jnp.asarray(std, dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _draw(leaves: Tuple[ParamDef, ...], key: jax.Array, dtype) -> list:
    # one program for the whole tree: one compile, not one per leaf shape
    keys = jax.random.split(key, len(leaves))
    return [_materialize(d, k, dtype) for d, k in zip(leaves, keys)]


def init_params(defs: Any, key: jax.Array, dtype=jnp.float32) -> Any:
    """Seeded random parameters at ``dtype`` (float32 masters for
    training; bfloat16 for serving, which computes in bf16)."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    return jax.tree.unflatten(treedef, _draw(tuple(leaves), key, dtype))


def param_specs(defs: Any) -> Any:
    """PartitionSpec tree (requires an active sharding_env)."""
    return jax.tree.map(
        lambda d: resolve_spec(d.shape, d.laxes, fsdp_hint=True),
        defs, is_leaf=is_def)


def param_count(defs: Any) -> int:
    return sum(d.size for d in jax.tree.leaves(defs, is_leaf=is_def))


def abstract_params(defs: Any, dtype=jnp.float32) -> Any:
    """ShapeDtypeStruct tree (for dry-run lowering without allocation)."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs, is_leaf=is_def)
