"""Blockwise fused (flash) attention forward kernel for TPU.

The perf-critical compute hot-spot of every assigned LM architecture.
Online-softmax attention with (bq, bk) tiling:

  grid = (batch, q_heads, num_q_blocks, num_kv_blocks)

The kv-block axis is the minor-most grid dimension, so for a fixed
(b, h, i) the kernel visits kv blocks sequentially while running
max / sum / weighted-accumulator live in VMEM scratch — the classic
flash-attention recurrence.  Causal masking is applied per-tile from
global row/col indices.  GQA/MQA is supported by mapping query head h to
kv head h // group_size in the k/v BlockSpec index maps.

Per-sequence length masking (the fused bucketed-prefill contract): with
``lengths`` (B,) the kernel additionally masks key columns at or beyond
``lengths[b]`` — prompts padded up to a power-of-two bucket attend only
to their real tokens.  ``lengths`` rides in as a scalar-prefetch operand
(the same mechanism the paged-attention kernel uses for block tables),
so the mask costs one SMEM read per tile, not a VMEM operand.

Prefix-KV masking (the chunked-prefill contract): with ``k_prefix`` /
``v_prefix`` (B, KVH, Sp, D) and ``prefix_lengths`` (B,), the chunk's
queries additionally attend over a sequence's *already-committed* KV —
the caller's gather of the paged arena — prepended to the chunk's own
keys.  Prefix columns are NOT causally masked (every real prefix
position precedes every chunk query position by construction); they are
masked only by ``prefix_lengths[b]``.  Chunk columns keep the causal +
``lengths`` mask, shifted by the static prefix capacity.  Both length
vectors ride as scalar-prefetch operands; a row with
``prefix_lengths[b] == 0`` degenerates exactly to the prefix-less
kernel.

Block sizes default to (bq, bk) = (256, 512) with head_dim up to 256:
q-tile 256x256xf32 (256 KB) + k,v tiles 512x256 (2x512 KB) + acc scratch
well under the ~16 MiB VMEM budget, MXU-aligned (multiples of 128).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

_NEG_INF = -1e30


def _flash_kernel(*refs, sm_scale: float, causal: bool, bq: int, bk: int,
                  seq_k: int, has_lengths: bool, seq_prefix: int = 0,
                  has_prefix: bool = False):
    if has_prefix:
        len_ref, plen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    elif has_lengths:
        len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs

    b = pl.program_id(0)
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = col < seq_k                                   # padding mask
    if has_prefix:
        # keys are [prefix ; chunk]: prefix columns mask only by the
        # per-sequence committed length (every real prefix position
        # precedes every chunk query); chunk columns keep the causal +
        # chunk-length mask, shifted by the static prefix capacity
        row = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cc = col - seq_prefix                            # chunk-local column
        chunk_ok = cc < len_ref[b]
        if causal:
            chunk_ok = chunk_ok & (cc <= row)
        # boolean algebra, not a select: Mosaic cannot select between
        # two i1 vectors (it truncates an i8 select to i1)
        in_prefix = col < seq_prefix
        mask = mask & ((in_prefix & (col < plen_ref[b]))
                       | (~in_prefix & chunk_ok))
    else:
        if has_lengths:
            mask = mask & (col < len_ref[b])             # per-sequence length
        if causal:
            row = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (col <= row)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                  # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: float | None = None,
                    block_q: int = 256, block_k: int = 512,
                    lengths: jax.Array | None = None,
                    k_prefix: jax.Array | None = None,
                    v_prefix: jax.Array | None = None,
                    prefix_lengths: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Fused attention forward.

    q: (B, H, Sq, D);  k, v: (B, KVH, Sk, D) with H % KVH == 0.
    ``lengths``: optional (B,) int32 valid kv lengths — columns at or
    beyond ``lengths[b]`` are masked (length-padded prefill batches; for
    well-defined rows every length must be >= 1 under ``causal``).
    ``k_prefix``/``v_prefix``: optional (B, KVH, Sp, D) already-committed
    KV the queries may attend over in full (no causal mask — the chunked
    prefill contract: every query sits at a position after the whole
    prefix), masked per row by ``prefix_lengths`` (B,) int32; rows with
    ``prefix_lengths[b] == 0`` see no prefix at all.  Requires
    ``lengths``.  Returns (B, H, Sq, D) in q.dtype.
    """
    has_prefix = k_prefix is not None
    sp = 0
    if has_prefix:
        assert v_prefix is not None and prefix_lengths is not None
        assert lengths is not None, "prefix-KV path requires lengths"
        sp = k_prefix.shape[2]
        k = jnp.concatenate([k_prefix, k], axis=2)
        v = jnp.concatenate([v_prefix, v], axis=2)
    b, h, sq, d = q.shape
    _, kvh, sk, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    if sm_scale is None:
        sm_scale = d ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    # explicit zero padding to block multiples: padded kv columns are
    # masked by seq_k below; padded q rows are sliced off the output.
    pq, pk = (-sq) % bq, (-sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk
    grid = (b, h, pl.cdiv(sq_p, bq), pl.cdiv(sk_p, bk))

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, bq=bq, bk=bk,
        seq_k=sk, has_lengths=lengths is not None, seq_prefix=sp,
        has_prefix=has_prefix)

    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    scratch_shapes = [
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, d), jnp.float32),
    ]
    # index maps shared by both dispatch modes: the trailing *_ absorbs
    # the scalar-prefetch ref PrefetchScalarGridSpec appends
    q_map = lambda b_, h_, i, j, *_: (b_, h_, i, 0)           # noqa: E731
    kv_map = lambda b_, h_, i, j, *_, g=group: (b_, h_ // g, j, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
    ]
    out_specs = pl.BlockSpec((1, 1, bq, d), q_map)
    if lengths is None:
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            interpret=interpret_mode(interpret),
        )(q, k, v)
    else:
        # lengths (and, on the chunked path, prefix_lengths) ride as
        # scalar-prefetch operands (SMEM), the same mechanism the
        # paged-attention kernel uses for block tables
        scalars = [lengths.astype(jnp.int32)]
        if has_prefix:
            scalars.append(prefix_lengths.astype(jnp.int32))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret_mode(interpret),
        )(*scalars, q, k, v)
    return out[:, :, :sq]
