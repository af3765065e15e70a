"""Jit'd wrappers for the D-RaNGe generator kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import drange, ref


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cols", "use_pallas", "interpret"))
def pim_random_u32(seed: jax.Array, n_rows: int, n_cols: int,
                   *, use_pallas: bool = False,
                   interpret: bool | None = None) -> jax.Array:
    if use_pallas:
        return drange.random_u32(seed, n_rows, n_cols, interpret=interpret)
    return ref.random_u32(seed, n_rows, n_cols)


@functools.partial(jax.jit, static_argnames=("n_rows", "n_cols", "use_pallas", "interpret"))
def pim_random_uniform(seed: jax.Array, n_rows: int, n_cols: int,
                       *, use_pallas: bool = False,
                       interpret: bool | None = None) -> jax.Array:
    """Uniform floats in [0, 1) from the top 24 bits."""
    u = pim_random_u32(seed, n_rows, n_cols, use_pallas=use_pallas, interpret=interpret)
    return (u >> jnp.uint32(8)).astype(jnp.float32) * (1.0 / (1 << 24))


def entropy_seed_from_trng(trng, stream: int = 0) -> jax.Array:
    """Fold 64 true-random bits from the (simulated-DRAM) D-RaNGe TRNG
    into a kernel seed — the bridge between the paper-faithful entropy
    source and the TPU block generator."""
    words = trng.random_u32(2)
    return jnp.asarray([words[0] ^ jnp.uint32(stream), words[1]], dtype=jnp.uint32)
