"""Jit'd wrapper for paged decode attention.

``paged_attention`` is the jitted public entry; ``paged_attention_inline``
is the same dispatch logic without the jit wrapper, for callers that are
already inside a compiled computation (the serving engine's fused decode
step traces it inside one outer ``jax.jit``).

Multi-round contract: the engine's persistent decode loop
(``decode_block_rounds=K``) traces this kernel inside a
``jax.lax.while_loop`` body, so ``lengths`` may be a *loop carry* (each
in-loop round advances live rows' lengths on device) while
``block_tables`` stays a loop constant spanning the pages reserved for
the whole K-token block.  Both are ordinary traced operands here —
nothing in the dispatch may specialize on their values, only on shapes;
use the ``_inline`` form for this (the jitted wrapper would nest a jit
inside the loop body).  Positions at or beyond ``lengths[b]`` are
masked, so the over-reserved tail pages of a mid-block sequence are
never attended.
"""

from __future__ import annotations

import functools

import jax

from . import paged_attention as pa, ref


def paged_attention_inline(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           sm_scale: float | None = None,
                           use_pallas: bool = True,
                           interpret: bool | None = None,
                           k_self: jax.Array | None = None,
                           v_self: jax.Array | None = None,
                           return_lse: bool = False):
    """Pallas-or-reference dispatch; see the kernel for the contract.

    ``k_self``/``v_self`` (B, KVH, D) merge the fresh current token
    in-kernel; ``return_lse`` also returns the (m, l) softmax stats.
    """
    if use_pallas:
        return pa.paged_attention(q, k_arena, v_arena, block_tables, lengths,
                                  sm_scale=sm_scale, interpret=interpret,
                                  k_self=k_self, v_self=v_self,
                                  return_lse=return_lse)
    return ref.paged_attention(q, k_arena, v_arena, block_tables, lengths,
                               sm_scale=sm_scale, k_self=k_self,
                               v_self=v_self, return_lse=return_lse)


paged_attention = functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "use_pallas", "interpret", "return_lse"),
)(paged_attention_inline)
