"""Jit'd wrapper for paged decode attention.

``paged_attention`` is the jitted public entry; ``paged_attention_inline``
is the same dispatch logic without the jit wrapper, for callers that are
already inside a compiled computation (the serving engine's fused decode
step traces it inside one outer ``jax.jit``).

Both take the stacked arena ``(layers, pages, page, KVH, D)`` and the
layer to read: a layer loop passes the same arena to every step, and the
kernel reads the pages in place.

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


def uses_kernel(use_pallas: bool) -> bool:
    """Whether decode attention runs the Pallas kernel: always on a TPU
    backend, elsewhere (interpreted) only when ``use_pallas`` asks."""
    return use_pallas or jax.default_backend() == "tpu"


def kv_slots_read(lengths, width: int, page_size: int,
                  use_pallas: bool) -> int:
    """KV token slots one launch reads for rows of ``lengths`` over a
    block table ``width`` pages wide: the pages below each row's length
    on the kernel, or the whole table per row on the reference, which
    gathers it."""
    if not uses_kernel(use_pallas):
        return len(lengths) * width * page_size
    return page_size * sum(min(-(-n // page_size), width) for n in lengths)


def paged_attention_inline(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, layer,
                           block_tables: jax.Array, lengths: jax.Array, *,
                           sm_scale: float | None = None,
                           use_pallas: bool = True,
                           interpret: bool | None = None,
                           k_self: jax.Array | None = None,
                           v_self: jax.Array | None = None,
                           return_lse: bool = False):
    """Kernel-or-reference dispatch (:func:`uses_kernel`); see the
    kernel for the contract.

    ``k_self``/``v_self`` (B, KVH, D) merge the fresh current token
    in-kernel; ``return_lse`` also returns the (m, l) softmax stats.
    """
    if uses_kernel(use_pallas):
        return pa.paged_attention(q, k_arena, v_arena, layer, block_tables,
                                  lengths, sm_scale=sm_scale,
                                  interpret=interpret, k_self=k_self,
                                  v_self=v_self, return_lse=return_lse)
    return ref.paged_attention(q, k_arena, v_arena, layer, block_tables,
                               lengths, sm_scale=sm_scale, k_self=k_self,
                               v_self=v_self, return_lse=return_lse)


paged_attention = functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "use_pallas", "interpret", "return_lse"),
)(paged_attention_inline)
