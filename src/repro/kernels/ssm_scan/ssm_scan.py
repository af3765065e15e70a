"""Pallas kernels for the SSM state arena.

The state arena is the recurrent-state analogue of the KV arena: one
constant-size row per live sequence, mutated in bulk once per decode
round.  The scatter below is the ``SSM_STATE_WRITE`` launch target —
``(layers, batch)`` grid, row coordinates scalar-prefetched so the
output BlockSpec lands each block exactly on its row (no
read-modify-write), arena aliased in/out so untouched rows never move.
"layers" here is ``groups * mamba_sublayers``: the whole depth of the
model writes in ONE launch, the same amortization argument as
``rowclone.kv_scatter``.

Row copy (copy-on-fork) and row init (init-on-free) do not get their own
kernels: a state row IS a page of a ``(L, R, *row)`` arena, so they ride the
existing RowClone ``page_copy_batched`` / ``page_init_batched`` kernels
(see ``ops.py``) — which is the point: fork and free traffic is RowClone
traffic, priced as such on the model-face replay.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode, page_spec, paged_view


def _state_scatter_kernel(row_idx_ref, new_ref, arena_ref, out_ref):
    # Grid: (layers, batch, parts).  The output BlockSpec lands this
    # block exactly on arena[l, rows[b]]; the body is a pure row write.
    del row_idx_ref, arena_ref
    out_ref[...] = new_ref[...]


def state_scatter(arena: jax.Array, rows: jax.Array, new: jax.Array,
                  *, interpret: bool | None = None) -> jax.Array:
    """Scatter fresh state rows: ``arena[l, rows[b]] <- new[l, b]``.

    arena: (layers, num_rows, *row); rows: (batch,) int32; new:
    (layers, batch, *row).  A row keeps its natural trailing dims and
    moves as one block (split along its leading dim when too large for
    VMEM), so any row shape compiles.  One launch covers every
    sublayer's state for every sequence in the round.  Duplicate rows
    are only valid with identical payloads (last grid iteration wins).
    """
    a, n = paged_view(arena), paged_view(new.astype(arena.dtype))
    new_spec, parts = page_spec(n, lambda b, rw: b)
    out_spec, _ = page_spec(a, lambda b, rw: rw[b])
    out = pl.pallas_call(
        _state_scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(a.shape[0], rows.shape[0], parts),
            in_specs=[
                new_spec,
                pl.BlockSpec(memory_space=pl.ANY),   # arena (aliased, unread)
            ],
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        input_output_aliases={2: 0},  # arena (after 1 prefetch + new) -> out
        interpret=interpret_mode(interpret),
    )(rows.astype(jnp.int32), n, a)
    return out.reshape(arena.shape)
