"""int8 chunked gradient compression (distributed-optimization trick).

For pure data-parallel replicated-gradient sync, an fp32 all-reduce moves
4 bytes/element twice across the wire.  This module implements the
classic compressed alternative inside `shard_map`:

  1. each replica splits the gradient into `world` equal segments,
  2. quantizes to int8 with one fp32 scale per (segment, block),
  3. `all_to_all` so replica r receives segment r from everyone,
  4. dequantize + fp32 tree-sum of its segment (exact accumulation),
  5. re-quantize the reduced segment and `all_gather`.

Wire bytes: ~1/4 of fp32 ring all-reduce (int8 payload + scales), at the
cost of one quantization error on the way in and one on the way out.
`psum_compressed` is a drop-in for `jax.lax.psum` over the given axis.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256  # elements per quantization block


def _quantize(x: jax.Array):
    """x: (..., n) fp32 -> (int8 codes, fp32 scales per block)."""
    n = x.shape[-1]
    pad = (-n) % BLOCK
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xp.reshape(*x.shape[:-1], -1, BLOCK)
    # all-zero blocks (e.g. the padding psum_compressed appends to reach
    # world*seg elements) must dequantize to EXACT zeros: a tiny additive
    # scale floor would keep codes at 0 here, but any future change that
    # divides by absmax directly would turn pads into NaN/garbage that the
    # all_to_all round trip then sums into real elements.  Guard with a
    # where(): zero blocks get scale 1.0 -> codes 0 -> dequantized 0.0.
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def _dequantize(codes: jax.Array, scale: jax.Array, n: int) -> jax.Array:
    xb = codes.astype(jnp.float32) * scale
    return xb.reshape(*codes.shape[:-2], -1)[..., :n]


def psum_compressed(x: jax.Array, axis_name: str) -> jax.Array:
    """Compressed mean-preserving sum over ``axis_name`` (callable inside
    shard_map).  x: any shape; flattened internally."""
    world = jax.lax.axis_size(axis_name)
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    seg = -(-n // world)
    flat = jnp.pad(flat, (0, seg * world - n)).reshape(world, seg)

    codes, scale = _quantize(flat)                       # (world, seg/B, B)
    # all_to_all: split dim 0, concat on a fresh leading axis
    codes_t = jax.lax.all_to_all(codes[None], axis_name, split_axis=1,
                                 concat_axis=0, tiled=False)[:, 0]
    scale_t = jax.lax.all_to_all(scale[None], axis_name, split_axis=1,
                                 concat_axis=0, tiled=False)[:, 0]
    # codes_t: (world, seg/B, B) — peer p's copy of MY segment
    mine = jnp.sum(_dequantize(codes_t, scale_t, seg), axis=0)  # fp32 exact sum

    codes_r, scale_r = _quantize(mine[None])
    codes_all = jax.lax.all_gather(codes_r[0], axis_name)       # (world, ...)
    scale_all = jax.lax.all_gather(scale_r[0], axis_name)
    full = _dequantize(codes_all, scale_all, seg).reshape(-1)[:n]
    return full.reshape(shape).astype(x.dtype)


def compressed_grad_sync(grads: Any, mesh, axis_name: str = "data") -> Any:
    """Tree-wise compressed all-reduce (replicated-gradient DP mode)."""
    from jax.sharding import PartitionSpec as P

    def sync(g):
        fn = functools.partial(psum_compressed, axis_name=axis_name)
        return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(g)

    return jax.tree.map(sync, grads)
