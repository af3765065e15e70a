"""RowClone Pallas kernels: bulk in-memory copy / initialization on TPU.

The TPU-native adaptation of RowClone (DESIGN.md SS2): bulk data movement
that never occupies the MXU/VPU with useful work — a pure streaming
HBM -> VMEM -> HBM pipeline.  Pallas double-buffers the grid automatically,
so with row-sized blocks this runs at HBM bandwidth, the TPU equivalent of
"copy at row-buffer speed instead of through the core".

Kernel family:

* ``copy``      — tile-streamed tensor copy.
* ``init``      — tile memset from an SMEM scalar (no read traffic at all).
* ``page_copy`` — arena page copy: ``arena[dst_page] <- arena[src_page]``
  for a batch of page pairs, with the page index list scalar-prefetched
  (the BlockSpec index_map reads it — the TPU version of the POC consuming
  a PiDRAM instruction's row-address operands).  The arena is aliased
  in/out, so untouched pages are never moved: this is the RowClone
  "data never leaves the memory device" property at the XLA buffer level.
  A 2D ``(pages, elems)`` arena is the one-layer case of the batched op.

Layer-batched variants (the batched PiM op scheduler's launch targets —
one fused dispatch regardless of layer count or batch size, the TPU
analogue of amortizing the POC handshake over a whole command batch):

* ``page_copy_batched`` / ``page_init_batched`` — the same page ops over
  a ``(layers, pages, *page)`` arena with a 3D grid: every layer's pages
  move in one launch instead of ``O(layers)`` separate calls.
* ``kv_scatter`` — write ``(layers, batch)`` fresh KV slots
  ``arena[l, pages[b], slots[b]] <- new[l, b]`` in one launch: one
  HBM->HBM DMA per slot covers every layer, with the (page, slot)
  coordinates scalar-prefetched (no read-modify-write, no VMEM hop).

Page ops keep the arena's natural trailing dims, ``(layers, pages,
*page)``, and move a page as one block whose last two dims equal the
array's — the shape Mosaic accepts for any page, tile-aligned or not
(``repro.kernels.common.page_block`` splits pages too large for VMEM).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, page_spec, paged_view

# Default tile: 8 MiB of f32 per block-pair (in+out) incl. double buffering
# stays well under the ~16 MiB v5e VMEM budget at (512, 1024) f32;
# bf16 halves it.
_BLOCK_ROWS = 512
_BLOCK_COLS = 1024


def _copy_kernel(src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def copy_2d(src: jax.Array, *, block_rows: int = _BLOCK_ROWS,
            block_cols: int = _BLOCK_COLS,
            interpret: bool | None = None) -> jax.Array:
    """Streamed copy of a 2D array (rows, cols)."""
    rows, cols = src.shape
    br, bc = min(block_rows, rows), min(block_cols, cols)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    return pl.pallas_call(
        _copy_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        interpret=interpret_mode(interpret),
    )(src)


def _init_kernel(val_ref, dst_ref):
    dst_ref[...] = jnp.full(dst_ref.shape, val_ref[0], dst_ref.dtype)


def init_2d(shape, value, dtype=jnp.float32, *,
            block_rows: int = _BLOCK_ROWS, block_cols: int = _BLOCK_COLS,
            interpret: bool | None = None) -> jax.Array:
    """Memset: write ``value`` into a fresh (rows, cols) buffer.

    Unlike ``jnp.full`` followed by ops, this is a single write-only pass
    (the calloc-vs-RowClone-Init distinction: no read-for-ownership).
    """
    rows, cols = shape
    br, bc = min(block_rows, rows), min(block_cols, cols)
    grid = (pl.cdiv(rows, br), pl.cdiv(cols, bc))
    val = jnp.asarray([value], dtype)
    return pl.pallas_call(
        _init_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret_mode(interpret),
    )(val)


def _move_kernel(*refs):
    # the index_maps route the source block onto the destination page;
    # the body is a pure block move (scalar-prefetch refs lead)
    refs[-1][...] = refs[-2][...]


def page_copy_batched(arena: jax.Array, src_pages: jax.Array,
                      dst_pages: jax.Array, *,
                      interpret: bool | None = None) -> jax.Array:
    """Copy ``arena[:, src_pages[i]] -> arena[:, dst_pages[i]]`` for all i
    across every layer in ONE launch.

    arena: (layers, num_pages, *page); src/dst_pages: (n,) int32.  The
    arena is aliased in/out, so the launch cost is independent of the
    number of layers (grid iterations stream, nothing re-dispatches).
    A page written in a batch must not be read later in the same batch
    (the op queue flushes between such a write and read).
    """
    shape = arena.shape
    a = paged_view(arena)
    layers = a.shape[0]
    n = src_pages.shape[0]
    in_spec, parts = page_spec(a, lambda i, s, d: s[i])
    out_spec, _ = page_spec(a, lambda i, s, d: d[i])
    out = pl.pallas_call(
        _move_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(layers, n, parts),
            in_specs=[in_spec], out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={2: 0},
        interpret=interpret_mode(interpret),
    )(src_pages.astype(jnp.int32), dst_pages.astype(jnp.int32), a)
    return out.reshape(shape)


def page_init_batched(arena: jax.Array, dst_pages: jax.Array, value,
                      *, interpret: bool | None = None) -> jax.Array:
    """Memset ``arena[:, dst_pages[i]] <- value`` across all layers in one
    launch (layer-batched RowClone-Init).  The fill is one page-sized
    block built outside the kernel; its block index never changes, so it
    is fetched once and only destination pages are written."""
    shape = arena.shape
    a = paged_view(arena)
    layers = a.shape[0]
    n = dst_pages.shape[0]
    out_spec, parts = page_spec(a, lambda i, d: d[i])
    bpage = out_spec.block_shape[2:]
    fill = jnp.full(bpage, value, a.dtype)
    out = pl.pallas_call(
        _move_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(layers, n, parts),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # arena (aliased, unread)
                pl.BlockSpec(bpage, lambda l, i, j, d: (0,) * len(bpage)),
            ],
            out_specs=out_spec),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={1: 0},
        interpret=interpret_mode(interpret),
    )(dst_pages.astype(jnp.int32), a, fill)
    return out.reshape(shape)


def _page_view(arena: jax.Array) -> jax.Array:
    """A 2D ``(pages, elems)`` arena as a one-layer batched arena whose
    pages are ``(elems // 128, 128)`` where that divides."""
    p, e = arena.shape
    page = (e // 128, 128) if e % 128 == 0 else (1, e)
    return arena.reshape((1, p) + page)


def page_copy(arena: jax.Array, src_pages: jax.Array, dst_pages: jax.Array,
              *, interpret: bool | None = None) -> jax.Array:
    """Copy ``arena[src_pages[i]] -> arena[dst_pages[i]]`` for all i.

    arena: (num_pages, page_elems); src/dst_pages: (n,) int32.
    The arena buffer is donated/aliased: XLA updates pages in place.
    """
    out = page_copy_batched(_page_view(arena), src_pages, dst_pages,
                            interpret=interpret)
    return out.reshape(arena.shape)


def page_init(arena: jax.Array, dst_pages: jax.Array, value,
              *, interpret: bool | None = None) -> jax.Array:
    """Memset ``arena[dst_pages[i]] <- value`` (RowClone-Init on pages)."""
    out = page_init_batched(_page_view(arena), dst_pages, value,
                            interpret=interpret)
    return out.reshape(arena.shape)


# DMAs a scatter keeps in flight: each start beyond this many first
# retires the oldest, so a prefill-sized batch never floods the queue
_SCATTER_WINDOW = 64


def _kv_scatter_kernel(page_idx_ref, slot_idx_ref, new_ref, arena_ref,
                       out_ref, sem):
    # One DMA per batch row moves that row's slot for every layer:
    # new[:, b] -> arena[:, pages[b], slots[b]], HBM to HBM.  All copies
    # share one semaphore and have one size, so any wait retires one.
    del arena_ref
    batch = new_ref.shape[1]

    def copy(b):
        return pltpu.make_async_copy(
            new_ref.at[:, b],
            out_ref.at[:, page_idx_ref[b], slot_idx_ref[b]], sem)

    def start(b, carry):
        @pl.when(b >= _SCATTER_WINDOW)
        def _retire():
            copy(0).wait()
        copy(b).start()
        return carry

    jax.lax.fori_loop(0, batch, start, 0)

    def drain(b, carry):
        copy(0).wait()
        return carry

    jax.lax.fori_loop(0, min(batch, _SCATTER_WINDOW), drain, 0)


def kv_scatter(arena: jax.Array, pages: jax.Array, slots: jax.Array,
               new: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Scatter fresh KV vectors: ``arena[l, pages[b], slots[b]] <- new[l, b]``.

    arena: (layers, num_pages, page_size, *slot); pages/slots: (batch,)
    int32; new: (layers, batch, *slot).  One launch writes every layer's
    slot for every sequence in the batch — the decode-round KV write is a
    single dispatch independent of ``layers`` and ``batch``.  The slot's
    trailing dims must be whole VMEM tiles on a TPU (KV slots are
    ``(kv_heads, head_dim)``).  Duplicate (page, slot) pairs must carry
    identical values (the engine's pad rows do).
    """
    return pl.pallas_call(
        _kv_scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # new
                pl.BlockSpec(memory_space=pl.ANY),   # arena (aliased, unread)
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={3: 0},
        interpret=interpret_mode(interpret),
    )(pages.astype(jnp.int32), slots.astype(jnp.int32),
      new.astype(arena.dtype), arena)
