"""Distribution layer: sharding-rule resolution, gradient compression
(multi-device via subprocess), pimolib TPU arena, data pipeline."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _compat import given, settings, st

from repro.distributed import sharding as sh
from repro.launch.mesh import make_local_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestShardingRules:
    def test_divisibility_fallback(self):
        mesh = make_local_mesh(1, 1)
        with sh.sharding_env(mesh):
            # axis size 1 -> everything replicated (no constraint effect)
            spec = sh.resolve_spec((8, 16), ("batch", "ff"))
            assert tuple(spec) == (None, None)

    def test_resolve_spec_with_fake_mesh(self):
        # abstract mesh via AbstractMesh is overkill; emulate by checking
        # the rule logic with a 1-device mesh and the rule table itself
        rules = sh.default_rules(multi_pod=True)
        assert rules["batch"] == ("pod", "data")
        assert rules["experts"] == ("model",)

    def test_shard_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        assert sh.shard(x, "batch", None) is x


class TestGradCompression:
    def test_quantize_roundtrip_error_bound(self, rng):
        from repro.distributed.compression import _quantize, _dequantize
        x = jnp.asarray(rng.normal(size=(3, 1000)).astype(np.float32)) * 5
        codes, scale = _quantize(x)
        back = _dequantize(codes, scale, 1000)
        err = np.abs(np.asarray(back - x))
        bound = np.asarray(scale).max() * 0.5 + 1e-6
        assert err.max() <= bound

    @settings(max_examples=5, deadline=None)
    @given(n=st.integers(10, 3000))
    def test_quantize_shapes(self, n):
        from repro.distributed.compression import _quantize, _dequantize
        x = jnp.linspace(-1, 1, n)[None]
        codes, scale = _quantize(x)
        assert _dequantize(codes, scale, n).shape == (1, n)

    @pytest.mark.slow
    def test_compressed_psum_close_to_exact_8dev(self):
        """Run in a subprocess with 8 host devices (4 on sub-8-core
        boxes).  XLA host collectives spin-wait, so device threads far
        beyond the core count deadlock rather than just slowing down —
        below 4 cores there is no reliable configuration (2-device host
        meshes deadlock outright in this jax version), so skip."""
        cores = os.cpu_count() or 1
        if cores < 4:
            pytest.skip("host-mesh collectives deadlock with <4 cores")
        world = 8 if cores >= 8 else 4
        prog = textwrap.dedent(f"""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={world}"
            world = {world}
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.distributed.compression import psum_compressed
            mesh = jax.make_mesh((world,), ("data",))
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.normal(size=(world, 257)).astype(np.float32))
            exact = jax.shard_map(lambda v: jax.lax.psum(v, "data"),
                                  mesh=mesh, in_specs=P("data"),
                                  out_specs=P(None), check_vma=False)(x)
            comp = jax.shard_map(
                lambda v: psum_compressed(v[0], "data")[None], mesh=mesh,
                in_specs=P("data"), out_specs=P("data"), check_vma=False)(x)
            err = np.abs(np.asarray(comp[0] - exact[0]))
            rel = err.max() / (np.abs(np.asarray(exact[0])).max() + 1e-9)
            assert rel < 0.02, rel
            print("OK", rel)
        """)
        env = dict(os.environ, PYTHONPATH=SRC)
        env.pop("JAX_PLATFORMS", None)
        out = subprocess.run([sys.executable, "-c", prog], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stdout + out.stderr
        assert "OK" in out.stdout

    def test_quantize_zero_block_exact(self):
        """An all-zero block must round-trip to EXACT zeros (scale floor
        regression: an additive epsilon on the scale is harmless, but
        padding blocks that dequantize to non-zero garbage would be
        summed into real elements by psum_compressed)."""
        from repro.distributed.compression import BLOCK, _dequantize, _quantize
        x = jnp.zeros((2, 3 * BLOCK), jnp.float32)
        codes, scale = _quantize(x)
        assert int(jnp.abs(codes).max()) == 0
        assert bool(jnp.all(jnp.isfinite(scale)))
        back = _dequantize(codes, scale, 3 * BLOCK)
        assert np.asarray(back == 0.0).all()

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 1000), seed=st.integers(0, 2**16))
    def test_quantize_preserves_exact_zeros(self, n, seed):
        """Elementwise property: wherever x is exactly 0.0, the int8
        round trip returns exactly 0.0 — including the implicit padding
        _quantize appends to fill the last block, and including blocks
        that are entirely zero."""
        from repro.distributed.compression import _dequantize, _quantize
        rng = np.random.default_rng(seed)
        x = rng.normal(size=n).astype(np.float32)
        x[rng.random(n) < 0.3] = 0.0
        if n > 4:  # force one fully-zero span crossing block math
            x[: n // 2] = 0.0
        codes, scale = _quantize(jnp.asarray(x)[None])
        back = np.asarray(_dequantize(codes, scale, n))[0]
        assert (back[x == 0.0] == 0.0).all()

    def test_psum_compressed_zero_and_pad_exact_1dev(self):
        """mesh=1 in-process run of the full all_to_all/all_gather
        pipeline: a length-257 input (pads to a second 256-block) with
        exact-zero tail must come back with that tail EXACTLY zero, and
        the non-zero part within one quantization step."""
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import psum_compressed
        mesh = make_local_mesh(1, 1)
        x = np.zeros(257, np.float32)
        x[:100] = np.linspace(-3, 3, 100, dtype=np.float32)
        out = jax.shard_map(lambda v: psum_compressed(v, "model"),
                            mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)(jnp.asarray(x))
        out = np.asarray(out)
        assert (out[100:] == 0.0).all()
        assert np.abs(out[:100] - x[:100]).max() <= (6 / 127) * 1.01


class TestTpuPimolib:
    def test_arena_copy_init_rand(self):
        from repro.core import make_tpu_arena, TpuLib, Blocking, OpReceipt
        arena = make_tpu_arena(num_slabs=2, pages_per_slab=8, page_elems=64,
                               dtype=jnp.float32)
        lib = TpuLib(arena)
        src, dst = arena.allocator.alloc_copy_pair(2)
        vals = jnp.arange(2 * 64, dtype=jnp.float32).reshape(2, 64)
        rec = lib.write(src, vals)
        assert isinstance(rec, OpReceipt) and rec.ok and rec.face == "jax"
        rec = lib.copy(src, dst, blocking=Blocking.FIN)
        assert rec.op == "rowclone_copy" and rec.n_ops == 2 and rec.launches >= 1
        np.testing.assert_array_equal(np.asarray(lib.read(dst)), vals)
        rec = lib.init(dst, 0.0, blocking=Blocking.FIN)
        assert rec.op == "rowclone_init" and rec.launches >= 1
        assert float(jnp.abs(lib.read(dst)).sum()) == 0.0
        r = lib.rand_u32(jnp.asarray([1, 2], jnp.uint32), 4, 16)
        assert r.shape == (4, 16) and r.dtype == jnp.uint32
        bits, rec = lib.rand(48)
        assert bits.shape == (48,) and set(np.unique(bits)) <= {0, 1}
        assert rec.op == "drange_rand" and rec.n_ops == 48
        # logical bits, exactly as DeviceLib counts them (no rounding to
        # whole words); rand_u32 counts its raw words separately
        assert lib.stats["rand_bits"] == 4 * 16 * 32 + 48
        # logical-op stats stay consistent with the queue's accounting
        assert lib.stats["copies"] == 2 and lib.stats["inits"] == 2
        assert lib.stats["writes"] == 2 and lib.stats["reads"] == 4
        assert lib.queue.stats["ops_enqueued"] == lib.queue.stats["ops_coalesced"] == 4

    def test_v1_page_aliases_retired(self):
        # the *_pages deprecation cycle (PR 3) is over: the aliases are
        # gone, so stale v1 call sites fail loudly instead of warning
        from repro.core import make_tpu_arena, TpuLib
        arena = make_tpu_arena(num_slabs=1, pages_per_slab=4, page_elems=8,
                               dtype=jnp.float32)
        lib = TpuLib(arena)
        for alias in ("copy_pages", "init_pages", "read_pages",
                      "write_pages"):
            assert not hasattr(lib, alias), alias

    def test_same_slab_constraint_enforced(self):
        from repro.core import make_tpu_arena, TpuLib
        from repro.core.allocator import PimAllocError
        arena = make_tpu_arena(num_slabs=2, pages_per_slab=4, page_elems=16)
        lib = TpuLib(arena)
        a = arena.allocator.alloc(1, group=0)
        b = arena.allocator.alloc(1, group=1)
        with pytest.raises(ValueError):
            lib.copy(a, b)

    def test_deferred_ops_coalesce_to_one_launch(self):
        # TpuLib routes through the batched PiM op scheduler: deferred
        # mode folds N copy calls into ONE coalesced launch
        from repro.core import make_tpu_arena, TpuLib, Blocking
        arena = make_tpu_arena(num_slabs=2, pages_per_slab=8, page_elems=64,
                               dtype=jnp.float32)
        lib = TpuLib(arena, deferred=True)
        pairs = [arena.allocator.alloc_copy_pair(1) for _ in range(3)]
        for i, (src, _) in enumerate(pairs):
            lib.write(src, jnp.full((1, 64), float(i + 1)))
        for src, dst in pairs:
            rec = lib.copy(src, dst)
            assert rec.deferred and rec.launches == 0
        assert lib.queue.launches_by_kind["page_copy"] == 0  # still queued
        assert lib.stats["copies"] == 3
        rec = lib.flush(Blocking.FIN)
        assert lib.queue.launches_by_kind["page_copy"] == 1  # one launch
        assert rec.launches == 1
        for i, (_, dst) in enumerate(pairs):
            np.testing.assert_array_equal(
                np.asarray(lib.read(dst)),
                np.full((1, 64), i + 1, np.float32))


class TestDataPipeline:
    def test_deterministic_replay(self):
        from repro.configs import ARCHS, ShapeConfig, reduced
        from repro.data.pipeline import PipelineConfig, SyntheticLM
        r = reduced(ARCHS["gemma-2b"])
        d1 = SyntheticLM(r, ShapeConfig("t", 64, 4, "train"), PipelineConfig(seed=9))
        d2 = SyntheticLM(r, ShapeConfig("t", 64, 4, "train"), PipelineConfig(seed=9))
        b1, b2 = d1.batch(17), d2.batch(17)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])

    def test_prefetcher(self):
        from repro.data.pipeline import Prefetcher
        it = Prefetcher(iter(range(10)), depth=3)
        assert list(it) == list(range(10))

    def test_labels_shifted(self):
        from repro.configs import ARCHS, ShapeConfig, reduced
        from repro.data.pipeline import PipelineConfig, SyntheticLM
        r = reduced(ARCHS["granite-3-8b"])
        d = SyntheticLM(r, ShapeConfig("t", 32, 2, "train"), PipelineConfig())
        b = d.batch(0)
        np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


class TestAllocatorProperties:
    @settings(max_examples=20, deadline=None)
    @given(ops=st.lists(st.tuples(st.booleans(), st.integers(1, 4)),
                        min_size=1, max_size=30))
    def test_never_double_allocates(self, ops):
        from repro.core.allocator import (PimAllocError, SubarrayAllocator,
                                          arena_groups)
        alloc = SubarrayAllocator(arena_groups(2, 16))
        live = []
        seen = set()
        for is_alloc, n in ops:
            if is_alloc or not live:
                try:
                    a = alloc.alloc(n)
                except PimAllocError:
                    continue
                for r in a.rows:
                    assert r not in seen
                    seen.add(r)
                live.append(a)
            else:
                a = live.pop()
                for r in a.rows:
                    seen.discard(r)
                alloc.free(a)
        assert alloc.free_rows() == 32 - len(seen)

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 8))
    def test_copy_pair_same_group(self, n):
        from repro.core.allocator import SubarrayAllocator, arena_groups
        alloc = SubarrayAllocator(arena_groups(4, 16))
        src, dst = alloc.alloc_copy_pair(n)
        assert src.group == dst.group
        assert not set(src.rows) & set(dst.rows)
