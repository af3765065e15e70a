"""Jit'd public wrappers for the SSM state-arena op family.

State arenas are ``(groups, sublayers, slots, ...)`` — conv windows and
SSD states keep their natural trailing dims; the wrappers merge the two
leading dims into the kernels' layer axis, ``(L, R, *row)``, and restore
on return.  As with RowClone, ``use_pallas`` selects the Pallas kernel
vs the pure-jnp reference (``interpret=None`` lets the backend decide
how the kernel runs), and an empty op batch is a no-op (no launch; the
scheduler never dispatches for it).

Row copy/init reuse the RowClone ``page_copy_batched`` /
``page_init_batched`` kernels — a state row is just a page of the
flattened arena, so copy-on-fork and init-on-free are literally RowClone
traffic (and the trace prices them as such).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rowclone import rowclone as rc_kernels

from . import ref, ssm_scan


def _flat3(a: jax.Array) -> jax.Array:
    """(groups, sublayers, slots, *row) -> (groups*sublayers, slots, *row)."""
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


def state_scatter_inline(arena: jax.Array, rows: jax.Array,
                         new: jax.Array, *, use_pallas: bool = False,
                         interpret: bool | None = None) -> jax.Array:
    """Write ``arena[:, :, rows[b]] <- new[:, :, b]`` in one launch.

    arena: (groups, sublayers, slots, ...); new: (groups, sublayers,
    batch, ...).  Un-jitted body so the engine's fused steps can trace
    it without a nested donation; ``pim_state_scatter`` is the
    jitted/donating wrapper the ``ssm_state_write`` flush executor uses.
    """
    if rows.shape[0] == 0:
        return arena
    a3 = _flat3(arena)
    n3 = new.reshape((a3.shape[0], rows.shape[0]) + a3.shape[2:])
    if not use_pallas:
        out = ref.state_scatter(a3, rows, n3)
    else:
        out = ssm_scan.state_scatter(a3, rows, n3.astype(arena.dtype),
                                     interpret=interpret)
    return out.reshape(arena.shape)


pim_state_scatter = functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret"),
    donate_argnums=(0,))(state_scatter_inline)


def state_gather_inline(arena: jax.Array, rows: jax.Array) -> jax.Array:
    """Read ``arena[:, :, rows[b]]`` -> (groups, sublayers, batch, ...).
    Reads have no Pallas variant (XLA fuses the gather into the
    surrounding step); only mutations are RowClone hot spots."""
    return arena[:, :, rows]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"),
                   donate_argnums=(0,))
def pim_state_copy(arena: jax.Array, src_rows: jax.Array,
                   dst_rows: jax.Array, *, use_pallas: bool = False,
                   interpret: bool | None = None) -> jax.Array:
    """Copy-on-fork: ``arena[:, :, src_rows[i]] -> arena[:, :, dst_rows[i]]``
    across every sublayer in one RowClone launch."""
    if src_rows.shape[0] == 0:
        return arena
    a3 = _flat3(arena)
    if not use_pallas:
        out = ref.row_copy(a3, src_rows, dst_rows)
    else:
        out = rc_kernels.page_copy_batched(a3, src_rows, dst_rows,
                                           interpret=interpret)
    return out.reshape(arena.shape)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"),
                   donate_argnums=(0,))
def pim_state_init(arena: jax.Array, dst_rows: jax.Array, value,
                   *, use_pallas: bool = False,
                   interpret: bool | None = None) -> jax.Array:
    """Init-on-free: memset ``arena[:, :, dst_rows[i]] <- value`` in one
    RowClone-Init launch (no cross-sequence state leakage)."""
    if dst_rows.shape[0] == 0:
        return arena
    a3 = _flat3(arena)
    if not use_pallas:
        out = ref.row_init(a3, dst_rows, value)
    else:
        out = rc_kernels.page_init_batched(a3, dst_rows, value,
                                           interpret=interpret)
    return out.reshape(arena.shape)
