"""Paged decode attention kernel over the PiM KV arena.

This is where PiDRAM's memory-management contribution meets the serving
path: the KV cache lives in a page arena managed by the subarray-aware
allocator (`repro.serving.kv_cache`), and decode attention walks each
sequence's *block table* — pages are never copied or compacted; forking a
sequence is a `pim_page_copy` (RowClone) and freeing is a `pim_page_init`.

Kernel layout (decode: one query token per sequence):

  grid = (batch, max_pages_per_seq)

Scalar-prefetched operands: block_tables (batch, max_pages) and context
lengths (batch,).  For grid step (b, p) the k/v BlockSpecs select arena
page ``block_tables[b, p]``; flash-style running (m, l, acc) scratch
accumulates across the page axis.  Pages beyond ``ceil(len/page)`` are
masked out entirely.

Two fusion hooks keep a decode round a single dispatch:

* ``k_self`` / ``v_self`` — the current token's fresh K/V (not yet
  written to the arena) are folded into the running softmax in the
  finalize step, so the engine needs no separate history-re-reading
  merge pass after the kernel;
* ``return_lse`` — the running log-sum-exp statistics ``(m, l)`` are
  emitted alongside the output so callers that *do* merge externally can
  combine without recomputing history scores.

q: (B, H, D) single token per sequence; kv arena: (pages, page_size, KVH, D).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

_NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  page_size: int, sm_scale: float, groups: int,
                  has_self: bool, return_lse: bool):
    # Optional refs unpack in in_specs/out_specs order: inputs
    # [k_self, v_self], outputs [o, m, l], then the three scratch refs.
    i = 0
    if has_self:
        ks_ref, vs_ref = rest[0], rest[1]
        i = 2
    o_ref = rest[i]
    i += 1
    if return_lse:
        m_ref, l_ref = rest[i], rest[i + 1]
        i += 2
    m_scr, l_scr, acc_scr = rest[i:i + 3]

    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    ctx_len = len_ref[b]

    @pl.when(p * page_size < ctx_len)
    def _attend():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # (H, D)
        k = k_ref[0].astype(jnp.float32)                     # (page, KVH, D)
        v = v_ref[0].astype(jnp.float32)                     # (page, KVH, D)
        h, d = q.shape
        kvh = k.shape[1]
        qg = q.reshape(kvh, groups, d)                       # (KVH, G, D)
        # scores: (KVH, G, page)
        s = jnp.einsum("kgd,pkd->kgp", qg, k)
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < ctx_len, s, _NEG_INF)

        m_prev = m_scr[...]                                  # (H, 1)
        m_cur = jnp.max(s, axis=2).reshape(h, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        ps = jnp.exp(s - m_new.reshape(kvh, groups, 1))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(ps, axis=2).reshape(h, 1)
        pv = jnp.einsum("kgp,pkd->kgd", ps, v).reshape(h, d)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(p == np_ - 1)
    def _finalize():
        m = m_scr[...]
        l = l_scr[...]
        acc = acc_scr[...]
        if has_self:
            # fold the current token (position ctx_len, always attended)
            # into the running softmax — the in-kernel self-token merge
            q = q_ref[0].astype(jnp.float32) * sm_scale      # (H, D)
            h, d = q.shape
            ks = ks_ref[0].astype(jnp.float32)               # (KVH, D)
            vs = vs_ref[0].astype(jnp.float32)
            kvh = ks.shape[0]
            qg = q.reshape(kvh, groups, d)
            # an elementwise product reduced over the lane dim: Mosaic
            # lays the (KVH, G) result out as it does the page scores
            s_self = jnp.sum(qg * ks[:, None, :], axis=2).reshape(h, 1)
            m_new = jnp.maximum(m, s_self)
            alpha = jnp.exp(m - m_new)
            p_self = jnp.exp(s_self - m_new)                 # (H, 1)
            l = l * alpha + p_self
            vsg = jnp.broadcast_to(vs[:, None, :], (kvh, groups, d))
            acc = acc * alpha + p_self * vsg.reshape(h, d)
            m = m_new
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
        if return_lse:
            m_ref[0] = m
            l_ref[0] = l


def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    sm_scale: float | None = None,
                    interpret: bool | None = None,
                    k_self: jax.Array | None = None,
                    v_self: jax.Array | None = None,
                    return_lse: bool = False):
    """Decode attention over a paged KV arena.

    q: (B, H, D); k_arena/v_arena: (pages, page_size, KVH, D);
    block_tables: (B, max_pages) int32; lengths: (B,) int32;
    k_self/v_self: optional (B, KVH, D) fresh current-token KV, merged
    in-kernel at position ``lengths[b]``.

    Returns o (B, H, D), or (o, m, l) with m/l (B, H) float32 running
    softmax stats when ``return_lse``.
    """
    bsz, h, d = q.shape
    pages, page_size, kvh, _ = k_arena.shape
    groups = h // kvh
    if sm_scale is None:
        sm_scale = d ** -0.5
    max_pages = block_tables.shape[1]
    grid = (bsz, max_pages)
    has_self = k_self is not None

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, sm_scale=sm_scale, groups=groups,
        has_self=has_self, return_lse=return_lse)

    in_specs = [
        pl.BlockSpec((1, h, d), lambda b, p, bt, ln: (b, 0, 0)),
        pl.BlockSpec((1, page_size, kvh, d), lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
        pl.BlockSpec((1, page_size, kvh, d), lambda b, p, bt, ln: (bt[b, p], 0, 0, 0)),
    ]
    operands = [q, k_arena, v_arena]
    if has_self:
        in_specs += [
            pl.BlockSpec((1, kvh, d), lambda b, p, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, kvh, d), lambda b, p, bt, ln: (b, 0, 0)),
        ]
        operands += [k_self, v_self]

    out_specs = pl.BlockSpec((1, h, d), lambda b, p, bt, ln: (b, 0, 0))
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        # (B, H, 1): a (1, H) block over (B, H) would have a unit
        # second-minor dim that is not the array's
        lse_spec = pl.BlockSpec((1, h, 1), lambda b, p, bt, ln: (b, 0, 0))
        lse_shape = jax.ShapeDtypeStruct((bsz, h, 1), jnp.float32)
        out_specs = [out_specs, lse_spec, lse_spec]
        out_shape = [out_shape, lse_shape, lse_shape]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)
    if return_lse:
        o, m, l = out
        return o, m[..., 0], l[..., 0]
    return out
