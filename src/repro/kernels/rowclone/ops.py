"""Jit'd public wrappers for the RowClone kernel family.

``use_pallas`` selects the Pallas kernel vs the pure-jnp reference;
``interpret=None`` lets the backend decide how the kernel runs
(``repro.kernels.common``).  Distribution-level code (dry-run, train,
serve) defaults to the jnp path — XLA already emits a fused copy/memset
for it — while the Pallas path is the TPU hot-spot implementation
validated against the reference in tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref, rowclone


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def pim_copy(src: jax.Array, *, use_pallas: bool = False,
             interpret: bool | None = None) -> jax.Array:
    """Bulk copy. 2D inputs stream through the Pallas kernel; other ranks
    reshape to 2D first (row-major pages)."""
    if not use_pallas:
        return ref.copy_2d(src)
    x2 = src.reshape(src.shape[0], -1) if src.ndim != 2 else src
    out = rowclone.copy_2d(x2, interpret=interpret)
    return out.reshape(src.shape)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "use_pallas", "interpret"))
def pim_init(shape, value, dtype=jnp.float32, *, use_pallas: bool = False,
             interpret: bool | None = None) -> jax.Array:
    if not use_pallas:
        return ref.init_2d(shape, value, dtype)
    import numpy as np
    flat = (int(np.prod(shape[:-1])), shape[-1]) if len(shape) != 2 else shape
    out = rowclone.init_2d(flat, value, dtype, interpret=interpret)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(0,))
def pim_page_copy(arena: jax.Array, src_pages: jax.Array, dst_pages: jax.Array,
                  *, use_pallas: bool = False, interpret: bool | None = None) -> jax.Array:
    """RowClone page copy inside a donated arena buffer."""
    if not use_pallas:
        return ref.page_copy(arena, src_pages, dst_pages)
    return rowclone.page_copy(arena, src_pages, dst_pages, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(0,))
def pim_page_init(arena: jax.Array, dst_pages: jax.Array, value,
                  *, use_pallas: bool = False, interpret: bool | None = None) -> jax.Array:
    if not use_pallas:
        return ref.page_init(arena, dst_pages, value)
    return rowclone.page_init(arena, dst_pages, value, interpret=interpret)


# ------------------------------------------------------------------ #
# Layer-batched launches — the batched PiM op scheduler's primitives.
# Arenas keep their natural trailing dims, (L, P, *page): the kernels
# move a page as one block of that shape.  An empty op batch is a no-op
# (no launch at all; the scheduler never dispatches for it).
# ------------------------------------------------------------------ #


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(0,))
def pim_page_copy_batched(arena: jax.Array, src_pages: jax.Array,
                          dst_pages: jax.Array, *, use_pallas: bool = False,
                          interpret: bool | None = None) -> jax.Array:
    """Copy ``arena[:, src_pages] -> arena[:, dst_pages]`` across all
    layers in one fused launch.  arena: (layers, pages, ...)."""
    if src_pages.shape[0] == 0:
        return arena
    if not use_pallas:
        return ref.page_copy_batched(arena, src_pages, dst_pages)
    return rowclone.page_copy_batched(arena, src_pages, dst_pages,
                                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"), donate_argnums=(0,))
def pim_page_init_batched(arena: jax.Array, dst_pages: jax.Array, value,
                          *, use_pallas: bool = False,
                          interpret: bool | None = None) -> jax.Array:
    if dst_pages.shape[0] == 0:
        return arena
    if not use_pallas:
        return ref.page_init_batched(arena, dst_pages, value)
    return rowclone.page_init_batched(arena, dst_pages, value,
                                      interpret=interpret)


def kv_scatter_inline(arena: jax.Array, pages: jax.Array, slots: jax.Array,
                      new: jax.Array, *, use_pallas: bool = False,
                      interpret: bool | None = None) -> jax.Array:
    """Write ``arena[:, pages[b], slots[b]] <- new[:, b]`` in one launch.

    arena: (layers, pages, page_size, ...); new: (layers, batch, ...).
    Un-jitted body, so callers already inside a compiled computation
    (the serving engine's fused decode step) can trace it without a
    nested donation; ``pim_kv_scatter`` is the jitted/donating wrapper.
    """
    if pages.shape[0] == 0:
        return arena
    new = new.reshape((arena.shape[0], pages.shape[0]) + arena.shape[3:])
    if not use_pallas:
        return ref.kv_scatter(arena, pages, slots, new)
    return rowclone.kv_scatter(arena, pages, slots, new, interpret=interpret)


pim_kv_scatter = functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret"),
    donate_argnums=(0,))(kv_scatter_inline)


def kv_gather_inline(arena: jax.Array, pages: jax.Array,
                     slots: jax.Array) -> jax.Array:
    """Read ``arena[:, pages[b], slots[b]]`` -> (layers, batch, ...) —
    the scatter's inverse, for callers already inside a compiled
    computation.

    The serving engine's multi-round decode loop uses this for its
    masked write-back: a sequence that stopped (EOS/budget) mid-block
    writes the value *already in its slot* back to it, so the scatter
    stays a structural no-op for dead rows and the arena is bit-identical
    to a round-at-a-time run.  Reads have no Pallas variant (XLA fuses
    the gather into the surrounding step); only mutations are RowClone
    hot spots.
    """
    return ref.kv_gather(arena, pages, slots)
