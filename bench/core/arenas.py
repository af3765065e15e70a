"""The device arrays of an engine's cache, named in this one place.

The cache holds its KV arenas and, where the architecture carries
recurrent state, a state arena (``cache.state``) with its conv windows and
SSD states.  The harness waits on them after the build, frees them before
the reference runs, rebuilds them as zeros between the seeds of one
engine, and hands them to the warm-up's dummy steps and init ops, all
through here.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp


def _named(cache) -> List[Tuple[object, str]]:
    """(owner, attribute) of every device array the cache holds."""
    out = [(cache, "k_arena"), (cache, "v_arena")]
    if cache.state is not None:
        out += [(cache.state, "conv"), (cache.state, "ssm")]
    return out


def arrays(cache) -> list:
    return [getattr(o, a) for o, a in _named(cache)]


def free(cache) -> None:
    for o, a in _named(cache):
        setattr(o, a, None)


def layout(cache) -> list:
    """(shape, dtype, sharding) of each array, for ``rebuild``."""
    return [(x.shape, x.dtype, x.sharding) for x in arrays(cache)]


def describe(cache) -> list:
    """Name, shape, dtype and bytes of each array, for the log: the
    cache's share of a cell's memory."""
    return [{"name": a if o is cache else f"state.{a}",
             "shape": list(x.shape), "dtype": str(x.dtype),
             "bytes": int(x.nbytes)}
            for (o, a), x in zip(_named(cache), arrays(cache))]


def rebuild(cache, shapes: list) -> None:
    """Every array as zeros of its ``layout``, as the cache builds them:
    zeros, then placed on the mesh where the cache has one."""
    for (o, a), (shape, dtype, sharding) in zip(_named(cache), shapes):
        z = jnp.zeros(shape, dtype)
        setattr(o, a, z if cache.mesh is None
                else jax.device_put(z, sharding))


def step_operands(cache, rows: int) -> tuple:
    """A fused step's arena operands for ``rows`` dummy rows: (k, v) and
    (conv, ssm, state slots), the state ones all on slot 0, or three
    Nones where the cache holds no state, as the engine passes them.
    The slots are converted from a Python list, as the engine converts
    its own (a program per length)."""
    st = cache.state
    state = ((None, None, None) if st is None else
             (st.conv, st.ssm, jnp.asarray([0] * rows, jnp.int32)))
    return (cache.k_arena, cache.v_arena), state


def adopt(cache, k, v, conv, ssm) -> None:
    """A step's arenas in place of those it was given, which it donates
    on a TPU."""
    cache.k_arena, cache.v_arena = k, v
    if cache.state is not None:
        cache.state.conv, cache.state.ssm = conv, ssm


def init_pages(cache, counts: Sequence[int], use_pallas: bool) -> None:
    """One page-init program per page count: zeros written over the top
    pages of both KV arenas, which nothing holds yet, with the op the
    cache flushes."""
    from repro.kernels.rowclone import ops as rc_ops
    top = cache.k_arena.shape[1]
    for n in counts:
        dst = jnp.asarray(list(range(top - n, top)), jnp.int32)
        cache.k_arena = rc_ops.pim_page_init_batched(
            cache.k_arena, dst, 0.0, use_pallas=use_pallas)
        cache.v_arena = rc_ops.pim_page_init_batched(
            cache.v_arena, dst, 0.0, use_pallas=use_pallas)
    jax.block_until_ready(cache.k_arena)


def init_state_rows(cache, counts: Sequence[int], use_pallas: bool) -> None:
    """The same for the state arena's rows; nothing where the cache
    holds no state."""
    from repro.kernels.ssm_scan import ops as ssm_ops
    st = cache.state
    if st is None:
        return
    for n in counts:
        dst = jnp.asarray(list(range(st.num_slots - n, st.num_slots)),
                          jnp.int32)
        st.conv = ssm_ops.pim_state_init(st.conv, dst, 0.0,
                                         use_pallas=use_pallas)
        st.ssm = ssm_ops.pim_state_init(st.ssm, dst, 0.0,
                                        use_pallas=use_pallas)
    jax.block_until_ready(st.ssm)


def zero(cache, use_pallas: bool) -> int:
    """Every arena zeroed whole with the cache's own init ops; returns
    how many programs that ran."""
    init_pages(cache, [cache.k_arena.shape[1]], use_pallas)
    if cache.state is None:
        return 1
    init_state_rows(cache, [cache.state.num_slots], use_pallas)
    return 2
