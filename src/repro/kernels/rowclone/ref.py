"""Pure-jnp oracles for the RowClone kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def copy_2d(src: jax.Array) -> jax.Array:
    return src + jnp.zeros((), src.dtype)  # forces a materialized copy


def init_2d(shape, value, dtype=jnp.float32) -> jax.Array:
    return jnp.full(shape, value, dtype)


def page_copy(arena: jax.Array, src_pages: jax.Array, dst_pages: jax.Array) -> jax.Array:
    return arena.at[dst_pages].set(arena[src_pages])


def page_init(arena: jax.Array, dst_pages: jax.Array, value) -> jax.Array:
    page = jnp.full((dst_pages.shape[0], arena.shape[1]), value, arena.dtype)
    return arena.at[dst_pages].set(page)


# Layer-batched variants: arena carries a leading (layers,) axis and every
# layer moves in the one logical op.


def page_copy_batched(arena: jax.Array, src_pages: jax.Array,
                      dst_pages: jax.Array) -> jax.Array:
    return arena.at[:, dst_pages].set(arena[:, src_pages])


def page_init_batched(arena: jax.Array, dst_pages: jax.Array, value) -> jax.Array:
    fill = jnp.full((arena.shape[0], dst_pages.shape[0]) + arena.shape[2:],
                    value, arena.dtype)
    return arena.at[:, dst_pages].set(fill)


def kv_scatter(arena: jax.Array, pages: jax.Array, slots: jax.Array,
               new: jax.Array) -> jax.Array:
    """arena: (L, P, S, *slot); pages/slots: (B,); new: (L, B, *slot)."""
    return arena.at[:, pages, slots].set(new.astype(arena.dtype))


def kv_gather(arena: jax.Array, pages: jax.Array, slots: jax.Array) -> jax.Array:
    """Read back ``arena[:, pages[b], slots[b]]`` — the scatter's inverse.
    arena: (L, P, S, *slot); pages/slots: (B,).  Returns (L, B, *slot)."""
    return arena[:, pages, slots]
