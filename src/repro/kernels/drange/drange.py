"""D-RaNGe Pallas kernel: block-parallel true-random bit generation.

TPU adaptation of D-RaNGe (DESIGN.md SS2): the DRAM activation-failure
entropy source does not exist on TPU, so the *generator* is a
counter-based PRNG (Threefry2x32, 20 rounds) seeded from the D-RaNGe
entropy pool (the simulated-DRAM TRNG supplies seeds; on a PiM-equipped
deployment those seeds would be hardware-true-random).  What is preserved
from the paper is the *system shape*: a block generator that refills a
random-number buffer asynchronously, drained by `pimolib.pim_rand`.

The kernel computes one VMEM tile of uint32 randoms per grid step:
  counter = tile_base + iota  ->  threefry2x32(key, counter)  ->  out tile
It is embarrassingly parallel and write-bandwidth-bound, like the
hardware technique it models.

Threefry2x32 is implemented with 32-bit add/xor/rotate only, so the same
code runs on the TPU VPU and in interpret mode, and `ref.py` is the exact
same arithmetic in plain jnp — oracles match bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)


def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """20-round Threefry2x32 on uint32 arrays (pure jnp; used by kernel
    body AND the reference oracle)."""
    ks = (k0, k1, k0 ^ k1 ^ _PARITY)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for block in range(5):
        rots = _ROTATIONS[block % 2]
        for r in rots:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(block + 1) % 3]
        x1 = x1 + ks[(block + 2) % 3] + np.uint32(block + 1)
    return x0, x1


def _drange_kernel(seed_ref, out_ref, *, block_elems: int):
    tile = pl.program_id(0)
    base = (tile * block_elems).astype(jnp.uint32)
    # 2D iota (TPU requires >=2D); flattened counter per element.
    shape = out_ref.shape
    row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    ctr = base + row * np.uint32(shape[1]) + col
    k0 = seed_ref[0]
    k1 = seed_ref[1]
    x0, _ = threefry2x32(k0, k1, ctr, ctr ^ np.uint32(0x9E3779B9))
    out_ref[...] = x0


def random_u32(seed: jax.Array, n_rows: int, n_cols: int,
               *, block_rows: int = 256,
               interpret: bool | None = None) -> jax.Array:
    """Generate (n_rows, n_cols) uint32 randoms from a (2,) uint32 seed."""
    br = min(block_rows, n_rows)
    grid = (pl.cdiv(n_rows, br),)
    kernel = functools.partial(_drange_kernel, block_elems=br * n_cols)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((br, n_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, n_cols), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(seed.astype(jnp.uint32))
