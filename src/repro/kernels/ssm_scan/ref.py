"""Pure-jnp references for the SSM state-arena ops.

A state arena is ``(groups * sublayers, slots, *row)`` after the ops
layer merges its leading dims — ``groups * sublayers`` is the "layer"
axis a launch streams over, ``slots`` the per-sequence state rows.  The
references work on any trailing row shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def state_scatter(arena: jax.Array, rows: jax.Array,
                  new: jax.Array) -> jax.Array:
    """``arena[:, rows[b]] <- new[:, b]``.  arena: (L, R, *row); rows:
    (B,); new: (L, B, *row).  Duplicate rows carry identical payloads by the
    caller's contract (padded batches duplicate row 0), so scatter order
    does not matter."""
    return arena.at[:, rows].set(new.astype(arena.dtype))


def state_gather(arena: jax.Array, rows: jax.Array) -> jax.Array:
    """``arena[:, rows[b]]`` -> (L, B, *row) — the scatter's inverse."""
    return arena[:, rows]


def row_copy(arena: jax.Array, src_rows: jax.Array,
             dst_rows: jax.Array) -> jax.Array:
    """Copy ``arena[:, src_rows[i]] -> arena[:, dst_rows[i]]`` — the
    copy-on-fork primitive.  All sources read pre-update state
    (destination rows are freshly allocated, so no chaining)."""
    return arena.at[:, dst_rows].set(arena[:, src_rows])


def row_init(arena: jax.Array, dst_rows: jax.Array, value) -> jax.Array:
    """Memset ``arena[:, dst_rows[i]] <- value`` — init-on-free."""
    shape = (arena.shape[0], dst_rows.shape[0]) + arena.shape[2:]
    return arena.at[:, dst_rows].set(jnp.full(shape, value, arena.dtype))
