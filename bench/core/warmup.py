"""Warm every program a cell's traffic can reach, before the window.

The engine buckets batch sizes, chunk lengths and block-table widths to
powers of two, and its page ops take one program per distinct page
count.  Each (bucket, width, count) is its own compiled program, and a
program first met inside the measured window would compile there.  So
the benchmark enumerates the reachable shapes from the deployment and
the traffic's length bounds, and runs each program once on dummy
operands: that traces it, loads it from the persistent compile cache
(or compiles it on a cold cache) and leaves it in the jit's own cache.

Dummy steps write into the arenas (``bench.core.arenas``: the KV pair
and, where the architecture carries recurrent state, the state arena),
so all are zeroed afterwards; inits write zeros over zeros and need
nothing.

This drives the engine's jitted step callables and the RowClone init-op
wrappers directly, with the operands the engine itself builds; it
changes nothing in the engine.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from bench.core import arenas


def pow2_upto(n: int) -> List[int]:
    out, b = [], 1
    while b < n:
        out.append(b)
        b *= 2
    return out + [b]


def bucket(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def widths(min_tokens: int, max_tokens: int, page: int) -> List[int]:
    """Block-table width buckets of sequences of these lengths."""
    lo = bucket(-(-min_tokens // page))
    hi = bucket(-(-max_tokens // page))
    return [w for w in pow2_upto(hi) if w >= lo]


def chunk_shapes(chunk: int, max_rows: int) -> List[Tuple[int, int]]:
    """(chunk bucket, row bucket) pairs a chunk batch can take: rows in
    one batch share a bucket sc and hold more than sc/2 tokens each
    within the round's budget of ``chunk`` tokens."""
    out = []
    for sc in pow2_upto(chunk):
        rows = chunk if sc == 1 else chunk // (sc // 2 + 1)
        out += [(sc, b) for b in pow2_upto(bucket(min(rows, max_rows)))]
    return out


def _i32(*shape):
    return jnp.asarray(np.zeros(shape, np.int32))


def _lst(n):
    # the engine builds its scatter plans from Python lists: the int32
    # conversion is a program of its own per length
    return jnp.asarray([0] * n, jnp.int32)


def _seed(eng):
    return eng.rng_seed + jnp.uint32(1)


def _temps(b):
    return jnp.asarray(np.zeros((b,), np.float32))


def decode(eng, batch: int, width: int) -> None:
    (k, v), (conv, ssm, srows) = arenas.step_operands(eng.cache, batch)
    tok, k, v, conv, ssm = eng._step(
        eng.params, _i32(batch, 1), k, v, _i32(batch, width),
        jnp.asarray(np.ones((batch,), np.int32)), _i32(batch), _i32(batch),
        _seed(eng), _temps(batch), conv, ssm, srows)
    arenas.adopt(eng.cache, k, v, conv, ssm)
    np.asarray(tok)


def chunk(eng, sc: int, rows: int, width: int) -> None:
    n = rows * sc
    (k, v), (conv, ssm, srows) = arenas.step_operands(eng.cache, rows)
    tok, _, k, v, conv, ssm = eng._chunk_step(
        eng.params, _i32(rows, sc), jnp.asarray(np.ones((rows,), np.int32)),
        _i32(rows), k, v, _i32(rows, width), _i32(rows),
        _lst(n), _lst(n), _lst(n), _seed(eng), _temps(rows), conv, ssm,
        srows, has_writes=True)
    arenas.adopt(eng.cache, k, v, conv, ssm)
    np.asarray(tok)


def prefill(eng, sp: int, rows: int) -> None:
    n = rows * sp
    (k, v), (conv, ssm, srows) = arenas.step_operands(eng.cache, rows)
    tok, _, k, v, conv, ssm = eng._prefill_step(
        eng.params, _i32(rows, sp), jnp.asarray(np.ones((rows,), np.int32)),
        k, v, _lst(n), _lst(n), _lst(n), _seed(eng),
        _temps(rows), conv, ssm, srows, has_writes=True)
    arenas.adopt(eng.cache, k, v, conv, ssm)
    np.asarray(tok)


def plan(dep: Dict, mix: Dict) -> Dict[str, list]:
    """Every program shape the deployment ``dep`` can reach under the
    traffic ``mix`` (see bench/traffic/*.json for the length bounds)."""
    page = dep["page_size"]
    lo, hi = mix["bounds"]["min_seq_tokens"], mix["bounds"]["max_seq_tokens"]
    ws = widths(lo, hi, page)
    shapes: Dict[str, list] = {
        "decode": [(b, w) for b in pow2_upto(dep["max_batch"])
                   if b >= dep.get("min_decode_batch", 1) for w in ws],
        "page_init": list(range(1, dep["max_page_init"] + 1)),
    }
    if dep.get("max_prefill_chunk"):
        shapes["chunk"] = [(sc, r, w) for sc, r in chunk_shapes(
            dep["max_prefill_chunk"], dep["max_chunk_rows"]) for w in ws]
    else:
        lens = mix["bounds"]["prompt_buckets"]
        shapes["prefill"] = [(sp, r) for sp in lens
                             for r in pow2_upto(dep["max_prefill_rows"])]
    return shapes


def run(eng, shapes: Dict[str, list]) -> int:
    """Run one of every planned program; returns how many."""
    n = 0
    for b, w in shapes.get("decode", []):
        decode(eng, b, w)
        n += 1
    for sc, r, w in shapes.get("chunk", []):
        chunk(eng, sc, r, w)
        n += 1
    for sp, r in shapes.get("prefill", []):
        prefill(eng, sp, r)
        n += 1
    # zero the arenas whole (the dummy steps wrote into them), with the
    # same init ops the cache uses
    n += arenas.zero(eng.cache, eng.use_pallas)
    counts = shapes.get("page_init", [])
    arenas.init_pages(eng.cache, counts, eng.use_pallas)
    # the cache zeroes a freed sequence's state row in a flush of its own
    # (PagedKVCache.free): one row at a time
    arenas.init_state_rows(eng.cache, [1], eng.use_pallas)
    return n + len(counts) + (eng.cache.state is not None)
