"""Pure-jnp oracle for paged decode attention.

Mirrors the Pallas kernel's contract — layer ``layer`` of a stacked
``(layers, pages, page, KVH, D)`` arena — including the fusion hooks: an
optional fresh current-token K/V (``k_self``/``v_self``) merged at
position ``lengths[b]``, and optional ``(m, l)`` running log-sum-exp
statistics (``return_lse``) defined exactly as the kernel accumulates
them (masked scores clamp to ``-1e30``; a fully-masked row has
``m = -1e30, l = 0``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    layer, block_tables: jax.Array, lengths: jax.Array, *,
                    sm_scale: float | None = None,
                    k_self: jax.Array | None = None,
                    v_self: jax.Array | None = None,
                    return_lse: bool = False):
    bsz, h, d = q.shape
    page_size, kvh = k_arena.shape[2:4]
    groups = h // kvh
    if sm_scale is None:
        sm_scale = d ** -0.5
    max_pages = block_tables.shape[1]
    max_len = max_pages * page_size

    # Gather each sequence's logical KV from its pages of ``layer``.
    k = k_arena[layer, block_tables]             # (B, P, page, KVH, D)
    v = v_arena[layer, block_tables]
    k = k.reshape(bsz, max_len, kvh, d)
    v = v.reshape(bsz, max_len, kvh, d)
    valid = jnp.arange(max_len)[None, :] < lengths[:, None]   # (B, S)
    if k_self is not None:
        # current token appended after the history; always attended
        k = jnp.concatenate([k, k_self[:, None].astype(k.dtype)], axis=1)
        v = jnp.concatenate([v, v_self[:, None].astype(v.dtype)], axis=1)
        valid = jnp.concatenate(
            [valid, jnp.ones((bsz, 1), bool)], axis=1)

    qg = q.reshape(bsz, kvh, groups, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32)) * sm_scale
    s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), _NEG_INF)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    out = out / jnp.where(l == 0.0, 1.0, l)[..., None]
    out = out.reshape(bsz, h, d).astype(q.dtype)
    if return_lse:
        return out, m.reshape(bsz, h), l.reshape(bsz, h)
    return out
