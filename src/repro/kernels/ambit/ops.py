"""Jit'd public wrappers for the Ambit bitwise kernel family.

``use_pallas`` selects the Pallas kernel vs the pure-jnp reference,
mirroring the RowClone wrappers (``interpret=None`` lets the backend
decide how the kernel runs).  Bitwise
ops are defined on *bit patterns*: float arenas are bitcast to a matching
unsigned view, operated on, and bitcast back, so both paths are bit-exact
for any storage dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ambit, ref

_UINT_FOR_ITEMSIZE = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _as_bits(arena: jax.Array):
    """Integer view of the arena plus the dtype to restore (or None)."""
    if jnp.issubdtype(arena.dtype, jnp.integer):
        return arena, None
    uint = _UINT_FOR_ITEMSIZE[arena.dtype.itemsize]
    return jax.lax.bitcast_convert_type(arena, uint), arena.dtype


@functools.partial(jax.jit, static_argnames=("op", "use_pallas", "interpret"),
                   donate_argnums=(0,))
def pim_page_bitwise_batched(arena: jax.Array, src_pages: jax.Array,
                             dst_pages: jax.Array, *, op: str,
                             use_pallas: bool = False,
                             interpret: bool | None = None) -> jax.Array:
    """``arena[:, dst[i]] <- op(arena[:, src[i]], arena[:, dst[i]])``
    (op in {"and", "or"}) or ``<- ~arena[:, src[i]]`` (op == "not"),
    across all layers in one fused launch.  arena: (layers, pages, ...)."""
    if src_pages.shape[0] == 0:
        return arena
    bits, orig_dtype = _as_bits(arena)
    if not use_pallas:
        if op == "not":
            out = ref.page_not_batched(bits, src_pages, dst_pages)
        else:
            out = ref.page_bitwise_batched(bits, src_pages, dst_pages, op)
    elif op == "not":
        out = ambit.page_not_batched(bits, src_pages, dst_pages,
                                     interpret=interpret)
    else:
        out = ambit.page_bitwise_batched(bits, src_pages, dst_pages, op,
                                         interpret=interpret)
    if orig_dtype is not None:
        out = jax.lax.bitcast_convert_type(out, orig_dtype)
    return out


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def pim_page_zero_scan(arena: jax.Array, pages: jax.Array, *,
                       use_pallas: bool = False,
                       interpret: bool | None = None) -> jax.Array:
    """Per-page zero-compare: returns bool (n,), True where
    ``arena[:, pages[i]]`` is all-zero bits across every layer.

    Read-only (the arena is NOT donated) — this is the eviction/audit
    scan, not a mutation."""
    if pages.shape[0] == 0:
        return jnp.zeros((0,), jnp.bool_)
    bits, _ = _as_bits(arena)
    if not use_pallas:
        return ref.page_zero_scan(bits, pages)
    return ambit.page_zero_scan(bits, pages, interpret=interpret) == 0
