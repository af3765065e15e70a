"""Paged decode attention that reads KV pages where they sit in the arena.

This is where PiDRAM's memory-management contribution meets the serving
path: the KV cache lives in a page arena managed by the subarray-aware
allocator (`repro.serving.kv_cache`), and decode attention walks each
sequence's *block table* — pages are never copied or compacted; forking a
sequence is a `pim_page_copy` (RowClone) and freeing is a `pim_page_init`.

The kernel takes the whole stacked arena ``(layers, pages, page, KVH,
D)`` and leaves it in HBM; the layer index is a scalar-prefetch operand
beside the block tables and lengths, so a layer loop hands the same
arena to every step and nothing slices, gathers or re-lays it out.

  grid = (batch,)

Grid step b walks row b's pages in blocks of ``pages_per_block`` pages,
and fetches only the pages below ``ceil(len / page)``: a block past the
row's length issues no DMA and no compute.  Each page is one contiguous
DMA (all KV heads of ``page`` tokens) into a double-buffered VMEM block,
so block i+1 — or the next row's first block — is in flight while block
i is computed.  The scores of one block are one MXU product of the row's
query heads against every (token, KV head) of the block, in the arena's
dtype with float32 accumulation; a query head keeps only its own KV
head's columns.  The flash-style running (m, l, acc) stay in float32.

Two fusion hooks keep a decode round a single dispatch:

* ``k_self`` / ``v_self`` — the current token's fresh K/V (not yet
  written to the arena) are folded into the running softmax after the
  last block, so the engine needs no separate merge pass;
* ``return_lse`` — the running log-sum-exp statistics ``(m, l)`` are
  emitted alongside the output for callers that merge externally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

_NEG_INF = -1e30

# most tokens one block holds: 32 pages of 16 tokens at granite's widths
# is 1 MiB of K and of V, 4 MiB of VMEM double-buffered.  On a v5e at
# granite's widths, 20 layers of 16 rows of 500-3,000 tokens took 3.83
# ms in blocks of 512 tokens, 4.00 in 256 and 4.75 in 128.
_BLOCK_TOKENS = 512


def pages_per_block(width: int, page_size: int) -> int:
    """Pages one block fetches for a block table ``width`` pages wide:
    the largest divisor of the width that holds at most
    ``_BLOCK_TOKENS`` tokens (at least one page)."""
    cap = max(1, _BLOCK_TOKENS // page_size)
    return max(d for d in range(1, min(width, cap) + 1) if width % d == 0)


def _dot(a, b, contract_b: int):
    """a (M, K) times b, contracting b's dim ``contract_b``, in the
    operands' dtype with float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (contract_b,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _paged_kernel(layer_ref, bt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest,
                  ppb: int, page: int, width: int, kvh: int, groups: int,
                  sm_scale: float, has_self: bool, return_lse: bool):
    # refs after the arenas, in in_specs/out_specs/scratch order:
    # [k_self, v_self], o, [m, l], k/v blocks, k/v semaphores, state.
    # lax throughout: each jnp wrapper is a nested jit to trace, and
    # every program that holds the kernel traces it again
    i = 0
    if has_self:
        ks_ref, vs_ref = rest[0], rest[1]
        i = 2
    o_ref = rest[i]
    i += 1
    if return_lse:
        m_ref, l_ref = rest[i], rest[i + 1]
        i += 2
    kbuf, vbuf, ksem, vsem, state = rest[i:i + 5]

    b = pl.program_id(0)
    rows = pl.num_programs(0)
    bk = ppb * page                     # tokens of one block
    layer = layer_ref[0]

    def n_pages(row):
        # a length past the table reads the whole table, as the oracle
        return lax.min(lax.div(len_ref[row] + page - 1, page), width)

    def n_blocks(row):
        return lax.div(n_pages(row) + ppb - 1, ppb)

    def each_page(row, blk, fn):
        # fn(j) for each page j of the block below the row's length; a
        # loop, not unrolled: every program that holds the kernel lowers
        # it again, and that time is set-up time
        left = lax.min(n_pages(row) - blk * ppb, ppb)
        lax.fori_loop(0, left, lambda j, c: (fn(j), c)[1], 0)

    def start(row, blk, slot):
        # one DMA per page, K and V
        def copy(j):
            pg = bt_ref[row * width + blk * ppb + j]
            pltpu.make_async_copy(k_hbm.at[layer, pg], kbuf.at[slot, j],
                                  ksem.at[slot]).start()
            pltpu.make_async_copy(v_hbm.at[layer, pg], vbuf.at[slot, j],
                                  vsem.at[slot]).start()
        each_page(row, blk, copy)

    def wait(buf, sem, row, blk, slot):
        each_page(row, blk, lambda j: pltpu.make_async_copy(
            k_hbm.at[0, 0], buf.at[slot, j], sem.at[slot]).wait())

    # state[0]: the slot of this row's first block; state[1]: whether
    # the previous row already started that block's copies
    @pl.when(b == 0)
    def _first_row():
        state[0] = 0
        state[1] = 0
        # a block's pages past its row's length are not fetched: their
        # slots keep an earlier block's pages, finite and masked.  Zero
        # both blocks once so that no slot is ever uninitialised.
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    nb = n_blocks(b)
    slot0 = state[0]

    @pl.when((nb > 0) & (state[1] == 0))
    def _fetch_first():
        start(b, 0, slot0)

    state[1] = 0
    q = q_ref[0]                                         # (H, D)
    h, d = q.shape
    n = len_ref[b]
    # the next row's first block is fetched during this row's last
    next_row = (b + 1 < rows) & (n_blocks(lax.min(b + 1, rows - 1)) > 0)

    def block(blk, carry):
        m, l, acc = carry
        slot = lax.rem(slot0 + blk, 2)
        more = blk + 1 < nb
        ahead = (blk + 1 == nb) & next_row

        @pl.when(more | ahead)
        def _fetch_next():
            start(lax.select(more, b, b + 1), lax.select(more, blk + 1, 0),
                  1 - slot)

        @pl.when(ahead)
        def _():
            state[1] = 1

        wait(kbuf, ksem, b, blk, slot)
        s = _dot(q, kbuf[slot].reshape(bk * kvh, d), 1) * sm_scale
        # column c holds token c // KVH of the block and KV head c % KVH;
        # query head r reads KV head r // groups
        col = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        own = lax.rem(col, kvh) == lax.div(
            lax.broadcasted_iota(jnp.int32, s.shape, 0), groups)
        s = lax.select(own & (lax.div(col, kvh) < n - blk * bk), s,
                       lax.full(s.shape, _NEG_INF, jnp.float32))
        m_new = lax.max(m, lax.reduce_max(s, (1,))[:, None])
        alpha = lax.exp(m - m_new)
        p = lax.exp(s - m_new)
        l = alpha * l + lax.reduce_sum(p, (1,))[:, None]
        wait(vbuf, vsem, b, blk, slot)
        v = vbuf[slot].reshape(bk * kvh, d)
        return m_new, l, acc * alpha + _dot(p.astype(v.dtype), v, 0)

    init = (lax.full((h, 1), _NEG_INF, jnp.float32),
            lax.full((h, 1), 0.0, jnp.float32),
            lax.full((h, d), 0.0, jnp.float32))
    m, l, acc = lax.fori_loop(0, nb, block, init)
    state[0] = lax.rem(slot0 + nb, 2)

    if has_self:
        # fold the current token (position len, always attended) into
        # the running softmax — the in-kernel self-token merge.  Query
        # head r takes column r // groups of the (H, KVH) products, and
        # a one-hot product gives it its KV head's value row.
        ks = ks_ref[0]                                   # (KVH, D)
        vs = vs_ref[0]
        hot = lax.broadcasted_iota(jnp.int32, (h, kvh), 1) == lax.div(
            lax.broadcasted_iota(jnp.int32, (h, kvh), 0), groups)
        s_self = _dot(q, ks, 1) * sm_scale
        s_self = lax.reduce_sum(
            lax.select(hot, s_self, lax.full(hot.shape, 0.0, jnp.float32)),
            (1,))[:, None]
        m_new = lax.max(m, s_self)
        alpha = lax.exp(m - m_new)
        p_self = lax.exp(s_self - m_new)                 # (H, 1)
        l = l * alpha + p_self
        acc = acc * alpha + p_self * _dot(hot.astype(vs.dtype), vs, 0)
        m = m_new
    l_safe = lax.select(l == 0.0, lax.full(l.shape, 1.0, jnp.float32), l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if return_lse:
        m_ref[0] = m
        l_ref[0] = l


def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    layer, block_tables: jax.Array, lengths: jax.Array, *,
                    sm_scale: float | None = None,
                    interpret: bool | None = None,
                    k_self: jax.Array | None = None,
                    v_self: jax.Array | None = None,
                    return_lse: bool = False):
    """Decode attention over layer ``layer`` of a stacked paged arena.

    q: (B, H, D); k_arena/v_arena: (layers, pages, page_size, KVH, D);
    layer: int32 scalar (may be traced); block_tables: (B, max_pages)
    int32, every entry a page of the arena; lengths: (B,) int32;
    k_self/v_self: optional (B, KVH, D) fresh current-token KV, merged
    in-kernel at position ``lengths[b]``.

    Returns o (B, H, D), or (o, m, l) with m/l (B, H) float32 running
    softmax stats when ``return_lse``.
    """
    bsz, h, d = q.shape
    n_layers, pages, page_size, kvh, _ = k_arena.shape
    groups = h // kvh
    if sm_scale is None:
        sm_scale = d ** -0.5
    width = block_tables.shape[1]
    ppb = pages_per_block(width, page_size)
    has_self = k_self is not None

    # one page as (page_size * KVH, D) rows: the same bytes as the
    # arena's (page_size, KVH, D), so the block is one matrix in VMEM
    flat = (n_layers, pages, page_size * kvh, d)
    kernel = functools.partial(
        _paged_kernel, ppb=ppb, page=page_size, width=width, kvh=kvh,
        groups=groups, sm_scale=sm_scale, has_self=has_self,
        return_lse=return_lse)

    row = lambda b, *_: (b, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, h, d), row),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [q, k_arena.reshape(flat), v_arena.reshape(flat)]
    if has_self:
        in_specs += [pl.BlockSpec((1, kvh, d), row),
                     pl.BlockSpec((1, kvh, d), row)]
        operands += [k_self.astype(k_arena.dtype),
                     v_self.astype(v_arena.dtype)]

    out_specs = pl.BlockSpec((1, h, d), row)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        # (B, H, 1): a (1, H) block over (B, H) would have a unit
        # second-minor dim that is not the array's
        lse_spec = pl.BlockSpec((1, h, 1), row)
        lse_shape = jax.ShapeDtypeStruct((bsz, h, 1), jnp.float32)
        out_specs = [out_specs, lse_spec, lse_spec]
        out_shape = [out_shape, lse_shape, lse_shape]

    block = (2, ppb, page_size * kvh, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM(block, k_arena.dtype),
            pltpu.VMEM(block, v_arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # rows run in order: a row prefetches the next row's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32).reshape(-1),
      lengths.astype(jnp.int32), *operands)
    if return_lse:
        o, m, l = out
        return o, m[..., 0], l[..., 0]
    return out
