"""What every Pallas kernel family shares: how a kernel runs, decided
when it is called, and the block over one arena page.

Every kernel takes ``interpret: bool | None``.  ``None``, the default on
every path, compiles the kernel with Mosaic on a TPU backend and runs the
Pallas interpreter on any other backend.  ``True`` is an explicit request
for the interpreter, which the CPU tests make; nothing on the serving
path passes it, so a chip never runs a kernel interpreted.  The backend
is asked at trace time, never while a module is imported.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental import pallas as pl


def interpret_mode(interpret: Optional[bool]) -> bool:
    """Resolve a kernel's ``interpret`` argument against the backend."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# bytes of one page block: in and out, double-buffered, stay far inside
# the 16 MiB of VMEM a v5e kernel may use
_PAGE_BLOCK_BYTES = 1 << 20


def page_block(page: tuple, itemsize: int) -> tuple:
    """A VMEM block over one arena page of shape ``page``.

    Mosaic takes a block whose last two dims equal the array's, so a
    page is moved whole.  A page of rank 3 or more that exceeds
    ``_PAGE_BLOCK_BYTES`` is split along its leading (untiled) dim into
    the fewest equal parts that fit.  Returns ``(block_page, n_parts)``."""
    nbytes = itemsize
    for d in page:
        nbytes *= d
    if len(page) < 3 or nbytes <= _PAGE_BLOCK_BYTES:
        return tuple(page), 1
    row = nbytes // page[0]
    for parts in range(1, page[0] + 1):
        if (page[0] % parts == 0
                and row * (page[0] // parts) <= _PAGE_BLOCK_BYTES):
            return (page[0] // parts,) + tuple(page[1:]), parts
    return (1,) + tuple(page[1:]), page[0]


def paged_view(arena: jax.Array) -> jax.Array:
    """View ``(layers, pages, elems)`` as ``(layers, pages, 1, elems)``:
    a block over a rank-1 page would have a unit second-minor dim that
    is not the array's.  Pages of rank >= 2 pass through unchanged."""
    if arena.ndim == 3:
        return arena.reshape(arena.shape[:2] + (1, arena.shape[2]))
    return arena


def page_spec(arena: jax.Array, page_at, *, layer_minor: bool = False):
    """BlockSpec over whole (or ``page_block``-split) pages of a
    ``(layers, pages, *page)`` arena.  The grid is ``(layers, i, part)``,
    or ``(i, layers, part)`` with ``layer_minor``; ``page_at(i, *s)``
    picks step i's page from the scalar-prefetch refs ``s``.  Returns
    ``(spec, n_parts)``."""
    page = arena.shape[2:]
    bpage, parts = page_block(page, arena.dtype.itemsize)
    rest = (0,) * (len(page) - 1)
    if layer_minor:
        def index(i, l, j, *s):
            return (l, page_at(i, *s), j) + rest
    else:
        def index(l, i, j, *s):
            return (l, page_at(i, *s), j) + rest
    return pl.BlockSpec((None, None) + bpage, index), parts
