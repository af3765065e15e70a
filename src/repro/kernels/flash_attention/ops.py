"""Jit'd wrapper for the flash-attention kernel family.

``attention`` is the jitted public entry; ``attention_inline`` is the
same dispatch logic without the jit wrapper, for callers already inside
a compiled computation (the serving engine's fused prefill step traces
it inside one outer ``jax.jit``).
"""

from __future__ import annotations

import functools

import jax

from . import flash_attention as fa, ref


def attention_inline(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, sm_scale: float | None = None,
                     block_q: int = 256, block_k: int = 512,
                     lengths: jax.Array | None = None,
                     k_prefix: jax.Array | None = None,
                     v_prefix: jax.Array | None = None,
                     prefix_lengths: jax.Array | None = None,
                     use_pallas: bool = True,
                     interpret: bool | None = None) -> jax.Array:
    """Pallas-or-reference dispatch; see the kernel for the contract.

    ``lengths`` (B,) masks key columns at or beyond each sequence's
    valid length (length-padded prefill batches).  ``k_prefix`` /
    ``v_prefix`` (B, KVH, Sp, D) + ``prefix_lengths`` (B,) add the
    chunked-prefill prefix-KV path: queries attend the committed prefix
    in full (no causal mask) and the chunk keys causally.
    """
    if use_pallas:
        return fa.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                  block_q=block_q, block_k=block_k,
                                  lengths=lengths, k_prefix=k_prefix,
                                  v_prefix=v_prefix,
                                  prefix_lengths=prefix_lengths,
                                  interpret=interpret)
    return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale,
                         lengths=lengths, k_prefix=k_prefix,
                         v_prefix=v_prefix, prefix_lengths=prefix_lengths)


attention = functools.partial(
    jax.jit, static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "use_pallas", "interpret"),
)(attention_inline)
