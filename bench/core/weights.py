"""Seeded weights, drawn on the device by the benchmark itself.

Every element is a pure function of (seed, leaf path, layer, index in
the layer): an integer hash turned into a uniform draw with the
standard deviation of the usual fan-in initialisation.  So the whole
model can be drawn in one jitted program, sharded where each chip's
shard lives, and the plain reference can draw any single layer again,
bit for bit, without taking anything the program under test made.

Seeds may exceed 32 bits: the high and low words are both mixed in.
"""

from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)


def _mix(x):
    """A 32-bit integer finaliser (lowbias32): every input bit moves
    about half of the output bits."""
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    return x ^ (x >> 16)


def seed_words(seed: int) -> jax.Array:
    """A seed of any size below 2**64 as two uint32 words (lo, hi): an
    operand, not a constant, so one compiled draw serves every seed."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)


def _key(words: jax.Array, leaf: str, layer) -> jax.Array:
    tag = np.uint32(zlib.crc32(leaf.encode()))
    k = _mix(words[1] ^ _mix(words[0] ^ _mix(jnp.uint32(tag))))
    return _mix(k ^ (jnp.asarray(layer, jnp.uint32) * np.uint32(0x9E3779B9)))


def leaf_std(path: str, shape: Sequence[int], d_model: int) -> float:
    """The common rule for the leaves architectures share: standard
    deviation of a weight by its role, from its path in the parameter
    tree.  Norms are ones (std 0), token tables 0.02, and an attention or
    MLP projection 1/sqrt(inputs summed into each output).  An
    architecture module's own ``leaf_std`` may defer to it."""
    name = path.rsplit("/", 1)[-1]
    if name in ("norm", "final_norm") or path.endswith("norm"):
        return 0.0
    if name in ("tok", "out"):
        return 0.02
    if name in ("wq", "wk", "wv", "up", "gate"):
        return 1.0 / math.sqrt(d_model)
    if name == "wo":              # (heads, head_dim, d)
        return 1.0 / math.sqrt(shape[-3] * shape[-2])
    if name == "down":            # (ff, d)
        return 1.0 / math.sqrt(shape[-2])
    raise ValueError(f"no initialisation rule for weight {path!r}")


def draw_leaf(words: jax.Array, path: str, shape: Tuple[int, ...], std: float,
              stacked: bool, dtype=jnp.bfloat16, layer=None) -> jax.Array:
    """One leaf from the seed's ``words``.  ``stacked`` leaves carry a
    leading layer axis; pass ``layer`` to draw that one layer alone
    (``shape`` then without the axis)."""
    if std == 0.0:
        return jnp.ones(shape, dtype)
    inner = shape[1:] if stacked and layer is None else shape
    strides = np.cumprod((1,) + tuple(inner[::-1]))[:-1][::-1]
    if stacked and layer is None:
        lay = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        dims = range(1, len(shape))
    else:
        lay = jnp.uint32(0 if layer is None else layer)
        dims = range(len(shape))
    idx = jnp.zeros(shape, jnp.uint32)
    for axis, stride in zip(dims, strides):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) \
            * np.uint32(stride)
    h = _mix(idx ^ _key(words, path, lay))
    # 23 bits centred on zero: an exact integer in float32, then one
    # rounding when scaled, so every caller gets the same bits
    centred = (h >> 9).astype(jnp.int32) - (1 << 22)
    scale = np.float32(std * math.sqrt(3.0) / (1 << 22))
    return (centred.astype(jnp.float32) * scale).astype(dtype)


def leaf_table(defs) -> Dict[str, Tuple[Tuple[int, ...], bool]]:
    """{path: (shape, stacked)} of a program's parameter-definition tree
    (leaves with ``shape`` and ``laxes``)."""
    out = {}
    flat = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda d: hasattr(d, "laxes"))[0]
    for kp, d in flat:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        out[path] = (tuple(d.shape), bool(d.laxes and d.laxes[0] == "layers"))
    return out


def draw_tree(defs, seed: int, d_model: int, std: Callable,
              out_shardings=None):
    """The whole parameter tree at bf16, in one jitted program, each leaf
    at the standard deviation ``std(path, shape, d_model)`` gives (the
    architecture module's ``leaf_std``); with ``out_shardings`` (a tree
    like ``defs``) each chip draws its shard."""
    table = leaf_table(defs)
    treedef = jax.tree.structure(defs, is_leaf=lambda d: hasattr(d, "laxes"))

    def build(words):
        leaves = [draw_leaf(words, p, s, std(p, s, d_model), st)
                  for p, (s, st) in table.items()]
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=out_shardings)(seed_words(seed))
