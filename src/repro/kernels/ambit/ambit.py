"""Ambit Pallas kernels: bulk in-arena bitwise ops on TPU.

The TPU-native adaptation of Ambit (Seshadri et al., MICRO'17): bulk
AND/OR/NOT over whole arena pages, executed where the data lives instead
of streaming operands through the core.  Like the RowClone page kernels,
the page index lists are scalar-prefetched (the BlockSpec index_maps read
them — the TPU version of the POC consuming an instruction's row-address
operands) and the arena is aliased in/out so untouched pages never move.

Kernel family (all layer-batched, one launch per op batch):

* ``page_bitwise_batched`` — ``arena[:, dst[i]] <- op(arena[:, src[i]],
  arena[:, dst[i]])`` for op in {and, or}: the two-operand in-place
  semantics of the AMB_AND/AMB_OR instructions (dst <- src OP dst).
* ``page_not_batched``     — ``arena[:, dst[i]] <- ~arena[:, src[i]]``
  (the dual-contact-cell NOT).
* ``page_zero_scan``       — per-page nonzero reduction over all layers:
  the in-arena analogue of OR-reducing candidate rows into a B-group
  scratch row and testing the result.  Read-only; returns int32 flags.

All kernels operate on integer (bit-pattern) arenas; the ops wrappers
bitcast float arenas to a matching unsigned view first, so results are
bit-exact regardless of storage dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, page_spec, paged_view


def _page_bitwise_batched_kernel(src_idx_ref, dst_idx_ref, src_view_ref,
                                 dst_view_ref, out_ref, *, op: str):
    # Grid: (layers, n_ops, parts).  The index_maps route the two input
    # views to arena[l, src[i]] / arena[l, dst[i]] and the output block
    # back onto arena[l, dst[i]]; the body is one VPU op.
    del src_idx_ref, dst_idx_ref
    if op == "and":
        out_ref[...] = src_view_ref[...] & dst_view_ref[...]
    else:
        out_ref[...] = src_view_ref[...] | dst_view_ref[...]


def page_bitwise_batched(arena: jax.Array, src_pages: jax.Array,
                         dst_pages: jax.Array, op: str, *,
                         interpret: bool | None = None) -> jax.Array:
    """``arena[:, dst[i]] <- op(arena[:, src[i]], arena[:, dst[i]])`` for
    all i across every layer in ONE launch.

    arena: (layers, num_pages, *page) integer dtype; src/dst_pages:
    (n,) int32.  The arena is passed as both operand views and aliased
    into the output, so only touched pages are rewritten.
    """
    if op not in ("and", "or"):
        raise ValueError(f"unknown ambit bitwise op {op!r}")
    shape = arena.shape
    a = paged_view(arena)
    n = src_pages.shape[0]
    src_spec, parts = page_spec(a, lambda i, s, d: s[i])
    dst_spec, _ = page_spec(a, lambda i, s, d: d[i])
    out = pl.pallas_call(
        functools.partial(_page_bitwise_batched_kernel, op=op),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(a.shape[0], n, parts),
            in_specs=[src_spec, dst_spec], out_specs=dst_spec),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={3: 0},  # dst view (after 2 prefetch args) -> out
        interpret=interpret_mode(interpret),
    )(src_pages.astype(jnp.int32), dst_pages.astype(jnp.int32), a, a)
    return out.reshape(shape)


def _page_not_batched_kernel(src_idx_ref, dst_idx_ref, arena_ref, out_ref):
    del src_idx_ref, dst_idx_ref
    out_ref[...] = ~arena_ref[...]


def page_not_batched(arena: jax.Array, src_pages: jax.Array,
                     dst_pages: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """``arena[:, dst[i]] <- ~arena[:, src[i]]`` across all layers in one
    launch (the dual-contact-cell NOT on pages)."""
    shape = arena.shape
    a = paged_view(arena)
    n = src_pages.shape[0]
    src_spec, parts = page_spec(a, lambda i, s, d: s[i])
    dst_spec, _ = page_spec(a, lambda i, s, d: d[i])
    out = pl.pallas_call(
        _page_not_batched_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(a.shape[0], n, parts),
            in_specs=[src_spec], out_specs=dst_spec),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={2: 0},
        interpret=interpret_mode(interpret),
    )(src_pages.astype(jnp.int32), dst_pages.astype(jnp.int32), a)
    return out.reshape(shape)


def _page_zero_scan_kernel(page_idx_ref, arena_ref, out_ref):
    # Grid: (n_pages, layers, parts) — the page index is OUTERMOST so
    # every revisit of a page's flag is consecutive; the flags live in
    # SMEM for the whole grid and are written back once at the end.
    del page_idx_ref
    i = pl.program_id(0)
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
    nz = jnp.max((arena_ref[...] != 0).astype(jnp.int32))
    out_ref[i] = jnp.where(first, nz, jnp.maximum(out_ref[i], nz))


def page_zero_scan(arena: jax.Array, pages: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """Per-page nonzero flags: ``out[i] = any(arena[:, pages[i]] != 0)``.

    arena: (layers, num_pages, *page) integer dtype; pages: (n,) int32.
    Returns (n,) int32 — 0 where the page is all-zero bits across every
    layer.  Read-only (no aliasing)."""
    a = paged_view(arena)
    n = pages.shape[0]
    spec, parts = page_spec(a, lambda i, p: p[i], layer_minor=True)
    return pl.pallas_call(
        _page_zero_scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n, a.shape[0], parts),
            in_specs=[spec],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret_mode(interpret),
    )(pages.astype(jnp.int32), a)
