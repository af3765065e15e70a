"""Kernel micro-benchmarks (CPU wall-times are NOT TPU predictions; they
exercise the code paths and report derived bandwidth-style metrics for
relative comparisons: pim copy/init vs naive jnp, TRNG rate, attention
impl variants)."""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, reps=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def main(out=sys.stdout):
    print("name,us_per_call,derived", file=out)

    # pim_copy vs naive gather-copy (arena is donated: thread it through,
    # as the serving engine does)
    from repro.kernels.rowclone import ops as rc
    import time as _time
    src = jnp.arange(8, dtype=jnp.int32)
    dst = jnp.arange(8, 16, dtype=jnp.int32)
    moved = 8 * 16384 * 4

    def timed_threaded(fn, reps=10):
        a = jnp.zeros((64, 16384), jnp.float32)
        a = jax.block_until_ready(fn(a))  # warmup + compile
        t0 = _time.perf_counter()
        for _ in range(reps):
            a = fn(a)
        jax.block_until_ready(a)
        return (_time.perf_counter() - t0) / reps * 1e6

    us = timed_threaded(lambda a: rc.pim_page_copy(a, src, dst))
    print(f"pim_page_copy_jnp,{us:.1f},{moved/us/1e3:.2f}GB/s", file=out)

    arena = jnp.zeros((64, 16384), jnp.float32)
    naive = jax.jit(lambda a: a.at[dst].set(a[src] * 1.0 + 0.0))
    us = timeit(naive, arena)
    print(f"naive_gather_copy,{us:.1f},{moved/us/1e3:.2f}GB/s", file=out)

    us = timed_threaded(lambda a: rc.pim_page_init(a, dst, 0.0))
    print(f"pim_page_init,{us:.1f},{moved/us/1e3:.2f}GB/s", file=out)

    # looped vs batched dispatch: the per-layer Python loop the serving
    # path used to run vs one fused launch over a (layers, pages, elems)
    # arena.  Reports dispatch counts and wall time per logical op-batch.
    L, P, E = 8, 64, 4096
    src_b = jnp.arange(4, dtype=jnp.int32)
    dst_b = jnp.arange(4, 8, dtype=jnp.int32)

    def looped_copy(a):   # L separate launches (the old path)
        for l in range(L):
            a = a.at[l].set(rc.pim_page_copy(a[l], src_b, dst_b))
        return a

    def batched_copy(a):  # 1 launch for all layers
        return rc.pim_page_copy_batched(a, src_b, dst_b)

    def timed_threaded_3d(fn, reps=10):
        a = jnp.zeros((L, P, E), jnp.float32)
        a = jax.block_until_ready(fn(a))
        t0 = _time.perf_counter()
        for _ in range(reps):
            a = fn(a)
        jax.block_until_ready(a)
        return (_time.perf_counter() - t0) / reps * 1e6

    us_loop = timed_threaded_3d(looped_copy)
    us_bat = timed_threaded_3d(batched_copy)
    print(f"page_copy_looped_{L}layers,{us_loop:.1f},{L}_dispatches", file=out)
    print(f"page_copy_batched_{L}layers,{us_bat:.1f},1_dispatch", file=out)
    print(f"page_copy_batch_speedup,{us_loop/us_bat:.2f},x", file=out)

    def looped_init(a):
        for l in range(L):
            a = a.at[l].set(rc.pim_page_init(a[l], dst_b, 0.0))
        return a

    us_loop = timed_threaded_3d(looped_init)
    us_bat = timed_threaded_3d(lambda a: rc.pim_page_init_batched(a, dst_b, 0.0))
    print(f"page_init_looped_{L}layers,{us_loop:.1f},{L}_dispatches", file=out)
    print(f"page_init_batched_{L}layers,{us_bat:.1f},1_dispatch", file=out)
    print(f"page_init_batch_speedup,{us_loop/us_bat:.2f},x", file=out)

    # KV scatter: B token slots across all layers in one launch vs B*L
    # per-slot dynamic-update launches
    B, S = 16, 16
    pages_b = jnp.arange(B, dtype=jnp.int32) % P
    slots_b = jnp.arange(B, dtype=jnp.int32) % S
    new_b = jnp.ones((L, B, E // S), jnp.float32)

    def looped_scatter(a):
        # the old engine path: one EAGER full-arena update per token
        # (B separate dispatches, each materializing the arena)
        for b in range(B):
            a = a.at[:, int(pages_b[b]), int(slots_b[b])].set(new_b[:, b])
        return a

    def timed_threaded_4d(fn, reps=10):
        a = jnp.zeros((L, P, S, E // S), jnp.float32)
        a = jax.block_until_ready(fn(a))
        t0 = _time.perf_counter()
        for _ in range(reps):
            a = fn(a)
        jax.block_until_ready(a)
        return (_time.perf_counter() - t0) / reps * 1e6

    us_loop = timed_threaded_4d(looped_scatter)
    us_bat = timed_threaded_4d(
        lambda a: rc.pim_kv_scatter(a, pages_b, slots_b, new_b))
    print(f"kv_write_looped_{B}tokens,{us_loop:.1f},{B}_updates", file=out)
    print(f"kv_scatter_batched_{B}tokens,{us_bat:.1f},1_dispatch", file=out)
    print(f"kv_scatter_batch_speedup,{us_loop/us_bat:.2f},x", file=out)

    # model-face dispatch accounting: POC handshakes looped vs batched
    from repro.core import (DRAMGeometry, EndToEndCosts, MemoryController,
                            SimulatedDRAM)
    mc = MemoryController(SimulatedDRAM(DRAMGeometry(4, 32)))
    costs = EndToEndCosts(mc)
    for n in (1, 8, 64):
        looped_ns = n * costs.rowclone_copy_ns(False)
        batched_ns = costs.rowclone_copy_batched_ns(n, False)
        print(f"poc_copy_looped_n{n},{looped_ns/1e3:.2f}us,{n}_handshakes",
              file=out)
        print(f"poc_copy_batched_n{n},{batched_ns/1e3:.2f}us,1_handshake",
              file=out)

    # the Pallas copy kernel: compiled on a TPU, interpreted elsewhere
    # (an interpreted time is the interpreter's cost, not the kernel's)
    from repro.kernels.rowclone import rowclone as rck
    x = jnp.ones((256, 1024), jnp.float32)
    us = timeit(lambda v: rck.copy_2d(v), x)
    print(f"pallas_copy_{jax.default_backend()},{us:.1f},", file=out)

    # D-RaNGe generator
    from repro.kernels.drange import ops as dr
    seed = jnp.asarray([1, 2], jnp.uint32)
    us = timeit(lambda s: dr.pim_random_u32(s, 256, 256), seed)
    rate = 256 * 256 * 32 / us  # bits/us
    print(f"pim_random_u32,{us:.1f},{rate:.0f}Mb/s", file=out)

    # attention impls (tiny shapes; relative only)
    from repro.models import attention as A
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 256, 8, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)).astype(np.float32))
    naive_fn = jax.jit(lambda q, k, v: A.naive_attention(q, k, v, causal=True))
    chunk_fn = jax.jit(lambda q, k, v: A.chunked_attention(
        q, k, v, causal=True, chunk_q=128, chunk_k=128))
    us = timeit(naive_fn, q, k, v)
    print(f"attention_naive_256,{us:.1f},", file=out)
    us = timeit(chunk_fn, q, k, v)
    print(f"attention_chunked_256,{us:.1f},", file=out)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
