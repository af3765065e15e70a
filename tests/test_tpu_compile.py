"""Compile guards: every Pallas kernel on the serving path, compiled by
the TPU compiler for a described (not attached) v5e chip at published
widths.

Interpret mode, which every other kernel test uses, accepts block
shapes, DMAs and vector ops that Mosaic refuses.  These tests hand the
kernels shapes only and call them with ``interpret=False``, so the chip's
own compiler checks tiling, memory spaces and VMEM use at no chip time.
Sizes: granite-3-8b (8 KV heads of 128, 32 query heads), 24 layers,
1024 pages of 16 slots, batch 16; the state arenas are jamba's.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels.ambit import ambit
from repro.kernels.drange import drange
from repro.kernels.flash_attention import flash_attention as fa
from repro.kernels.paged_attention import paged_attention as pa
from repro.kernels.rowclone import rowclone as rc
from repro.kernels.ssm_scan import ssm_scan
from repro.serving.kv_cache import _mamba_layout

LAYERS, PAGES, PAGE, KVH, HEADS, HD, BATCH = 24, 1024, 16, 8, 32, 128, 16
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_tpu(topo):
    """Compile ``fn`` for one described v5e chip from shapes alone and
    return its HLO text.  The persistent compilation cache is off: such a
    compile is written to it but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    chip = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes, donate=()):
        args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
            .as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was)


KV_ARENA = ((LAYERS, PAGES, PAGE, KVH, HD), BF16)
IDX = ((BATCH,), I32)


def _assert_kernel(hlo):
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k_self,return_lse,kvh", [
    (False, False, KVH), (True, False, KVH), (False, True, KVH),
    (True, True, KVH),
    # a quarter of granite's KV heads: one shard of a four-way mesh
    (True, False, KVH // 4)])
def test_paged_attention(compile_tpu, k_self, return_lse, kvh):
    def fn(q, ka, va, li, bt, lens, ks, vs):
        return pa.paged_attention(q, ka, va, li, bt, lens, interpret=False,
                                  k_self=ks if k_self else None,
                                  v_self=vs if k_self else None,
                                  return_lse=return_lse)
    heads = HEADS * kvh // KVH
    arena = ((LAYERS, PAGES, PAGE, kvh, HD), BF16)
    _assert_kernel(compile_tpu(
        fn, ((BATCH, heads, HD), BF16), arena, arena, ((), I32),
        ((BATCH, 64), I32), IDX, ((BATCH, kvh, HD), BF16),
        ((BATCH, kvh, HD), BF16)))


def test_decode_step_reads_the_arena_in_place(compile_tpu, topo,
                                              monkeypatch):
    """The whole decode step at granite widths, as one chip serves it
    (20 layers, 3,584 pages, batch 16, a 256-page table): the layer loop
    hands the stacked arena to the paged kernel, so no op outputs an
    arena layer or a gathered KV.  A step that slices each layer out of
    the scan's xs and gathers through the jnp reference holds 405.6 MB
    of temporaries at these shapes; this one holds under 16 MB."""
    import dataclasses
    import functools
    import re

    from repro.configs import ParallelConfig
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.models import transformer as T
    from repro.models.params import abstract_params
    from repro.serving import engine as E

    # the described chip is not the backend here: take the kernel the
    # TPU backend would take
    monkeypatch.setattr(pa_ops, "uses_kernel", lambda use_pallas: True)
    layers, pages, width = 20, 3584, 256
    cfg = dataclasses.replace(ARCHS["granite-3-8b"], num_layers=layers)
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          abstract_params(T.model_defs(cfg), BF16))
    arena = sds((layers, pages, PAGE, KVH, HD), BF16)
    idx = sds((BATCH,), I32)
    step = functools.partial(
        E._fused_decode_step, cfg,
        ParallelConfig(attention_impl="naive", remat="none"),
        use_pallas=False, interpret=False)
    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(
        params, sds((BATCH, 1), I32), arena, arena, sds((BATCH, width), I32),
        idx, idx, idx, sds((2,), jnp.uint32), sds((BATCH,), jnp.float32)
    ).compile()
    hlo = compiled.as_text()
    _assert_kernel(hlo)
    for shape in (f"bf16[{pages},{PAGE},{KVH},{HD}]",
                  f"bf16[{BATCH},{width * PAGE},{KVH},{HD}]",
                  f"bf16[{BATCH},{width * PAGE + 1},{KVH},{HD}]"):
        assert not re.search(r"= " + re.escape(shape) + r"\{", hlo), shape
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("prefix", [False, True])
def test_flash_attention(compile_tpu, prefix):
    b, s, sp = 2, 512, 1024

    def fn(q, k, v, lens, kp, vp, plens):
        if not prefix:
            return fa.flash_attention(q, k, v, lengths=lens, interpret=False)
        return fa.flash_attention(q, k, v, lengths=lens, k_prefix=kp,
                                  v_prefix=vp, prefix_lengths=plens,
                                  interpret=False)
    kv = ((b, KVH, s, HD), BF16)
    pkv = ((b, KVH, sp, HD), BF16)
    _assert_kernel(compile_tpu(fn, ((b, HEADS, s, HD), BF16), kv, kv,
                               ((b,), I32), pkv, pkv, ((b,), I32)))


def test_kv_scatter(compile_tpu):
    fn = lambda a, p, s, n: rc.kv_scatter(a, p, s, n, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, KV_ARENA, IDX, IDX,
                               ((LAYERS, BATCH, KVH, HD), BF16), donate=(0,)))


@pytest.mark.parametrize("batched", [True, False])
def test_page_copy(compile_tpu, batched):
    op = rc.page_copy_batched if batched else rc.page_copy
    arena = KV_ARENA if batched else ((PAGES, PAGE * KVH * HD), BF16)
    fn = lambda a, s, d: op(a, s, d, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, arena, IDX, IDX, donate=(0,)))


@pytest.mark.parametrize("batched", [True, False])
def test_page_init(compile_tpu, batched):
    op = rc.page_init_batched if batched else rc.page_init
    arena = KV_ARENA if batched else ((PAGES, PAGE * KVH * HD), BF16)
    fn = lambda a, d: op(a, d, 0.0, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, arena, IDX, donate=(0,)))


# state rows are large at jamba widths (an SSD state is 8 MB a row and
# sublayer), so the state arenas hold 8 slots and a round writes 4
STATE_IDX = ((4,), I32)


def _state_arenas():
    """The engine's (layers, slots, *row) state arenas at jamba widths:
    conv windows (W-1, channels) in bf16, SSD states (heads, P, N) in
    float32 (``PagedStateArena`` merges groups and sublayers)."""
    cfg = ARCHS["jamba-1.5-large-398b"]
    G, M = _mamba_layout(cfg)
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    slots = 8
    conv = ((G * M, slots, s.conv_width - 1, d_in + 2 * s.state_dim), BF16)
    ssm = ((G * M, slots, d_in // s.head_dim, s.head_dim, s.state_dim),
           jnp.float32)
    return {"conv": conv, "ssm": ssm}


@pytest.mark.parametrize("which", ["conv", "ssm"])
def test_state_scatter(compile_tpu, which):
    shape, dtype = _state_arenas()[which]
    new = ((shape[0], STATE_IDX[0][0]) + shape[2:], dtype)
    fn = lambda a, r, n: ssm_scan.state_scatter(a, r, n, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, (shape, dtype), STATE_IDX, new,
                               donate=(0,)))


@pytest.mark.parametrize("which", ["conv", "ssm"])
def test_state_copy_init(compile_tpu, which):
    # copy-on-fork and init-on-free ride the RowClone page kernels
    arena = _state_arenas()[which]
    _assert_kernel(compile_tpu(
        lambda a, s, d: rc.page_copy_batched(a, s, d, interpret=False),
        arena, STATE_IDX, STATE_IDX, donate=(0,)))
    _assert_kernel(compile_tpu(
        lambda a, d: rc.page_init_batched(a, d, 0.0, interpret=False),
        arena, STATE_IDX, donate=(0,)))


def test_page_zero_scan(compile_tpu):
    # the ops layer hands the kernel the arena's bit pattern
    shape, _ = KV_ARENA
    fn = lambda a, p: ambit.page_zero_scan(a, p, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, (shape, jnp.uint16), IDX))


def test_drange(compile_tpu):
    fn = lambda s: drange.random_u32(s, BATCH, 128, interpret=False)  # noqa: E731
    _assert_kernel(compile_tpu(fn, ((2,), jnp.uint32)))


def test_granite_widths_match_config():
    # the constants above are granite-3-8b's published widths
    cfg = ARCHS["granite-3-8b"]
    assert (cfg.num_kv_heads, cfg.num_heads,
            cfg.resolved_head_dim) == (KVH, HEADS, HD)


def test_kernel_bodies_hold_no_checkout_path(topo, monkeypatch, tmp_path):
    """The persistent compile cache keys a program on each kernel's
    Mosaic body, which keeps the source locations of the frames that
    traced it.  After ``enable_compile_cache`` they are relative to the
    checkout, so one commit keys the same programs wherever it is
    checked out."""
    import base64
    import re

    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    chip = SingleDeviceSharding(topo.devices[0])

    def body():
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        arena = sds((2, 8, PAGE, KVH, HD), BF16)
        text = jax.jit(
            lambda q, ka, va, bt, lens: pa.paged_attention(
                q, ka, va, 1, bt, lens, interpret=False)
        ).lower(sds((2, HEADS, HD), BF16), arena, arena, sds((2, 4), I32),
                sds((2,), I32)).as_text()
        enc = re.search(r'body\\22: \\22([A-Za-z0-9+/=]+)', text).group(1)
        return base64.b64decode(enc)

    root = str(compile_cache.ROOT).encode()
    try:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
        assert root in body()
        compile_cache.enable_compile_cache()
        relative = body()
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
    assert root not in relative
    assert b"src/repro/kernels/paged_attention/paged_attention.py" in relative
