"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
with hypothesis shape/dtype sweeps (fixed-example sweeps when
hypothesis is not installed; see tests/_compat.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _compat import given, settings, st

from repro.kernels.rowclone import ref as rc_ref, rowclone as rc
from repro.kernels.drange import ref as dr_ref, drange as dr
from repro.kernels.flash_attention import ref as fa_ref, flash_attention as fa
from repro.kernels.paged_attention import ref as pa_ref, paged_attention as pa

SETTINGS = dict(max_examples=10, deadline=None)


class TestRowClone:
    @settings(**SETTINGS)
    @given(rows=st.integers(4, 96), cols=st.integers(8, 300),
           dtype=st.sampled_from([jnp.float32, jnp.bfloat16, jnp.int32]))
    def test_copy_matches_ref(self, rows, cols, dtype):
        x = jnp.arange(rows * cols).reshape(rows, cols).astype(dtype)
        out = rc.copy_2d(x, block_rows=16, block_cols=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(out, np.float32),
                                      np.asarray(rc_ref.copy_2d(x), np.float32))

    @settings(**SETTINGS)
    @given(rows=st.integers(4, 64), cols=st.integers(8, 200),
           value=st.floats(-10, 10, allow_nan=False))
    def test_init_matches_ref(self, rows, cols, value):
        out = rc.init_2d((rows, cols), value, jnp.float32,
                         block_rows=16, block_cols=64, interpret=True)
        np.testing.assert_allclose(out, rc_ref.init_2d((rows, cols), value),
                                   rtol=1e-6)

    @settings(**SETTINGS)
    @given(n_pages=st.integers(4, 24), elems=st.integers(16, 256),
           n_copies=st.integers(1, 6), seed=st.integers(0, 99))
    def test_page_copy_matches_ref(self, n_pages, elems, n_copies, seed):
        n_copies = min(n_copies, n_pages // 2)  # need disjoint src/dst sets
        rng = np.random.default_rng(seed)
        arena = jnp.asarray(rng.normal(size=(n_pages, elems)).astype(np.float32))
        pages = rng.permutation(n_pages)
        src = jnp.asarray(pages[:n_copies].astype(np.int32))
        dst = jnp.asarray(pages[n_copies:2 * n_copies].astype(np.int32))
        out = rc.page_copy(arena, src, dst, interpret=True)
        np.testing.assert_array_equal(out, rc_ref.page_copy(arena, src, dst))

    def test_page_init_matches_ref(self):
        arena = jnp.ones((8, 128), jnp.float32)
        dst = jnp.asarray([1, 5], jnp.int32)
        out = rc.page_init(arena, dst, 0.0, interpret=True)
        np.testing.assert_array_equal(out, rc_ref.page_init(arena, dst, 0.0))


class TestRowCloneBatched:
    """Layer-batched page ops + KV scatter: one launch, all layers."""

    @settings(**SETTINGS)
    @given(layers=st.integers(1, 4), n_pages=st.integers(4, 16),
           elems=st.integers(16, 200), n_copies=st.integers(1, 5),
           seed=st.integers(0, 99))
    def test_page_copy_batched_matches_ref(self, layers, n_pages, elems,
                                           n_copies, seed):
        n_copies = min(n_copies, n_pages // 2)
        rng = np.random.default_rng(seed)
        arena = jnp.asarray(
            rng.normal(size=(layers, n_pages, elems)).astype(np.float32))
        pages = rng.permutation(n_pages)
        src = jnp.asarray(pages[:n_copies].astype(np.int32))
        dst = jnp.asarray(pages[n_copies:2 * n_copies].astype(np.int32))
        out = rc.page_copy_batched(arena, src, dst, interpret=True)
        np.testing.assert_array_equal(
            out, rc_ref.page_copy_batched(arena, src, dst))

    @settings(**SETTINGS)
    @given(layers=st.integers(1, 4), n_init=st.integers(1, 6),
           value=st.floats(-5, 5, allow_nan=False))
    def test_page_init_batched_matches_ref(self, layers, n_init, value):
        arena = jnp.ones((layers, 12, 96), jnp.float32)
        dst = jnp.asarray(
            np.random.default_rng(n_init).permutation(12)[:n_init].astype(np.int32))
        out = rc.page_init_batched(arena, dst, value, interpret=True)
        np.testing.assert_allclose(
            out, rc_ref.page_init_batched(arena, dst, value), rtol=1e-6)

    @settings(**SETTINGS)
    @given(layers=st.integers(1, 4), batch=st.integers(1, 6),
           ps=st.sampled_from([4, 8, 16]), elems=st.sampled_from([16, 48, 64]),
           seed=st.integers(0, 99))
    def test_kv_scatter_matches_ref(self, layers, batch, ps, elems, seed):
        rng = np.random.default_rng(seed)
        arena = jnp.asarray(
            rng.normal(size=(layers, 8, ps, elems)).astype(np.float32))
        # unique (page, slot) pairs — duplicate pairs are undefined
        flat = rng.permutation(8 * ps)[:batch]
        pages = jnp.asarray((flat // ps).astype(np.int32))
        slots = jnp.asarray((flat % ps).astype(np.int32))
        new = jnp.asarray(
            rng.normal(size=(layers, batch, elems)).astype(np.float32))
        out = rc.kv_scatter(arena, pages, slots, new, interpret=True)
        np.testing.assert_array_equal(
            out, rc_ref.kv_scatter(arena, pages, slots, new))

    def test_single_layer(self):
        arena = jnp.arange(2 * 64, dtype=jnp.float32).reshape(1, 2, 64)
        out = rc.page_copy_batched(arena, jnp.asarray([0], jnp.int32),
                                   jnp.asarray([1], jnp.int32),
                                   interpret=True)
        np.testing.assert_array_equal(out[0, 1], arena[0, 0])

    def test_non_aligned_page_elems(self):
        # page_elems not a multiple of the VMEM lane width: the page
        # still moves as one whole block
        arena = jnp.asarray(np.random.default_rng(3).normal(
            size=(2, 6, 100)).astype(np.float32))
        src = jnp.asarray([0, 2], jnp.int32)
        dst = jnp.asarray([1, 3], jnp.int32)
        out = rc.page_copy_batched(arena, src, dst, interpret=True)
        np.testing.assert_array_equal(
            out, rc_ref.page_copy_batched(arena, src, dst))

    def test_duplicate_destination_pages_init(self):
        # duplicate destinations are well-defined for init (same fill)
        arena = jnp.ones((2, 8, 32), jnp.float32)
        dst = jnp.asarray([3, 3, 5], jnp.int32)
        out = rc.page_init_batched(arena, dst, 0.0, interpret=True)
        assert float(jnp.abs(out[:, [3, 5]]).sum()) == 0.0
        assert float(jnp.abs(out[:, [0, 1, 2, 4, 6, 7]] - 1.0).sum()) == 0.0

    def test_empty_op_batch_is_noop(self):
        from repro.kernels.rowclone import ops as rc_ops
        arena = jnp.ones((2, 4, 3, 16), jnp.float32)
        empty = jnp.asarray([], jnp.int32)
        out = rc_ops.pim_page_copy_batched(arena, empty, empty)
        np.testing.assert_array_equal(out, jnp.ones((2, 4, 3, 16)))
        out = rc_ops.pim_page_init_batched(out, empty, 0.0)
        np.testing.assert_array_equal(out, jnp.ones((2, 4, 3, 16)))
        out = rc_ops.pim_kv_scatter(out, empty, empty,
                                    jnp.zeros((2, 0, 16), jnp.float32))
        np.testing.assert_array_equal(out, jnp.ones((2, 4, 3, 16)))

    def test_wrapper_pallas_matches_jnp_path(self):
        from repro.kernels.rowclone import ops as rc_ops
        rng = np.random.default_rng(11)
        arena = jnp.asarray(rng.normal(size=(3, 10, 4, 2, 8)).astype(np.float32))
        pages = jnp.asarray([1, 4, 7], jnp.int32)
        slots = jnp.asarray([0, 3, 2], jnp.int32)
        new = jnp.asarray(rng.normal(size=(3, 3, 2, 8)).astype(np.float32))
        a = rc_ops.pim_kv_scatter(arena.copy(), pages, slots, new,
                                  use_pallas=True, interpret=True)
        b = rc_ops.pim_kv_scatter(arena.copy(), pages, slots, new,
                                  use_pallas=False)
        np.testing.assert_array_equal(a, b)


class TestDRange:
    @settings(**SETTINGS)
    @given(rows=st.integers(1, 60), cols=st.sampled_from([16, 64, 128]),
           s0=st.integers(0, 2**32 - 1), s1=st.integers(0, 2**32 - 1))
    def test_kernel_bitexact_vs_ref(self, rows, cols, s0, s1):
        seed = jnp.asarray([s0, s1], jnp.uint32)
        out = dr.random_u32(seed, rows, cols, block_rows=16, interpret=True)
        expect = dr_ref.random_u32(seed, rows, cols)
        assert (np.asarray(out) == np.asarray(expect)).all()

    def test_statistical_quality(self):
        seed = jnp.asarray([7, 9], jnp.uint32)
        out = np.asarray(dr.random_u32(seed, 256, 64, interpret=True))
        bits = np.unpackbits(out.view(np.uint8))
        assert abs(bits.mean() - 0.5) < 0.01
        # chi-square-lite on bytes
        counts = np.bincount(out.view(np.uint8).ravel(), minlength=256)
        assert counts.std() / counts.mean() < 0.1

    def test_distinct_seeds_distinct_streams(self):
        a = dr.random_u32(jnp.asarray([1, 2], jnp.uint32), 16, 16, interpret=True)
        b = dr.random_u32(jnp.asarray([1, 3], jnp.uint32), 16, 16, interpret=True)
        assert (np.asarray(a) != np.asarray(b)).any()


class TestFlashAttention:
    @settings(**SETTINGS)
    @given(b=st.integers(1, 3), h=st.sampled_from([2, 4]),
           kvh=st.sampled_from([1, 2]), sq=st.integers(8, 130),
           sk=st.integers(8, 130), d=st.sampled_from([16, 32]),
           causal=st.booleans())
    def test_matches_naive(self, b, h, kvh, sq, sk, d, causal):
        if h % kvh:
            h = kvh * (h // kvh or 1)
        rng = np.random.default_rng(b * 1000 + sq)
        q = jnp.asarray(rng.normal(size=(b, h, sq, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, kvh, sk, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, kvh, sk, d)).astype(np.float32))
        out = fa.flash_attention(q, k, v, causal=causal, block_q=32,
                                 block_k=32, interpret=True)
        expect = fa_ref.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, expect, rtol=3e-5, atol=3e-5)

    def test_bf16(self):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 4, 64, 32))).astype(jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(2, 2, 64, 32))).astype(jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(2, 2, 64, 32))).astype(jnp.bfloat16)
        out = fa.flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                                 interpret=True)
        expect = fa_ref.attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_lengths_mask_matches_ref(self):
        # per-sequence valid-length masking (the fused bucketed-prefill
        # contract): kernel vs jnp oracle, and the masked rows must
        # equal an unpadded run of the same prompts
        rng = np.random.default_rng(3)
        b, h, kvh, s, d = 3, 4, 2, 40, 16
        q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
        lens = jnp.asarray([40, 23, 9], jnp.int32)
        out = fa.flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=16, lengths=lens, interpret=True)
        expect = fa_ref.attention(q, k, v, causal=True, lengths=lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=3e-5, atol=3e-5)
        # row 2's valid prefix matches the unpadded single-sequence run
        n = 9
        solo = fa_ref.attention(q[2:3, :, :n], k[2:3, :, :n], v[2:3, :, :n],
                                causal=True)
        np.testing.assert_allclose(np.asarray(out[2, :, :n]),
                                   np.asarray(solo[0]),
                                   rtol=3e-5, atol=3e-5)


class TestFlashPrefixKV:
    """The chunked-prefill prefix-KV path: chunk queries attend causally
    over the chunk plus non-causally over already-committed prefix KV
    with its own per-row length mask."""

    @staticmethod
    def _qkv(rng, b, h, kvh, s, d):
        q = jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, kvh, s, d)).astype(np.float32))
        return q, k, v

    def test_composes_with_full_sequence_oracle(self):
        # split a full causal attention at s0: prefix KV + chunk queries
        # through the prefix path must equal the full run's chunk rows —
        # the prefix and chunk masks compose into plain causal attention
        rng = np.random.default_rng(7)
        b, h, kvh, s, s0, d = 2, 4, 2, 24, 10, 16
        q, k, v = self._qkv(rng, b, h, kvh, s, d)
        lens = jnp.asarray([24, 17], jnp.int32)
        full = fa_ref.attention(q, k, v, causal=True, lengths=lens)
        qc, kc, vc = q[:, :, s0:], k[:, :, s0:], v[:, :, s0:]
        kp, vp = k[:, :, :s0], v[:, :, :s0]
        plens = jnp.asarray([s0, s0], jnp.int32)
        clens = lens - s0
        for impl, kw in ((fa_ref.attention, {}),
                         (fa.flash_attention,
                          dict(block_q=8, block_k=8, interpret=True))):
            out = impl(qc, kc, vc, causal=True, lengths=clens,
                       k_prefix=kp, v_prefix=vp, prefix_lengths=plens, **kw)
            for bi in range(b):
                n = int(clens[bi])       # rows past lens are undefined
                np.testing.assert_allclose(
                    np.asarray(out)[bi, :, :n],
                    np.asarray(full)[bi, :, s0:s0 + n],
                    rtol=3e-5, atol=3e-5)

    def test_empty_prefix_degenerates_to_plain_path(self):
        # prefix_lengths == 0 must reproduce the prefix-less kernel
        # exactly (the PR 4 fused-prefill behavior)
        rng = np.random.default_rng(8)
        b, h, kvh, s, sp, d = 2, 4, 2, 16, 12, 16
        q, k, v = self._qkv(rng, b, h, kvh, s, d)
        kp = jnp.asarray(rng.normal(size=(b, kvh, sp, d)).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(b, kvh, sp, d)).astype(np.float32))
        lens = jnp.asarray([16, 11], jnp.int32)
        zero = jnp.zeros((b,), jnp.int32)
        plain = fa.flash_attention(q, k, v, causal=True, block_q=8,
                                   block_k=8, lengths=lens, interpret=True)
        with_pref = fa.flash_attention(q, k, v, causal=True, block_q=8,
                                       block_k=8, lengths=lens, k_prefix=kp,
                                       v_prefix=vp, prefix_lengths=zero,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(with_pref), np.asarray(plain),
                                   rtol=1e-6, atol=1e-6)

    def test_kernel_matches_ref_ragged(self):
        # per-row ragged prefix AND chunk lengths, tile sizes that force
        # kv blocks to straddle the prefix/chunk boundary: kernel vs ref
        rng = np.random.default_rng(9)
        b, h, kvh, sc, sp, d = 3, 4, 2, 20, 24, 16
        q, kc, vc = self._qkv(rng, b, h, kvh, sc, d)
        kp = jnp.asarray(rng.normal(size=(b, kvh, sp, d)).astype(np.float32))
        vp = jnp.asarray(rng.normal(size=(b, kvh, sp, d)).astype(np.float32))
        plens = jnp.asarray([0, 13, 24], jnp.int32)
        lens = jnp.asarray([20, 7, 1], jnp.int32)
        out = fa.flash_attention(q, kc, vc, causal=True, block_q=8,
                                 block_k=16, lengths=lens, k_prefix=kp,
                                 v_prefix=vp, prefix_lengths=plens,
                                 interpret=True)
        expect = fa_ref.attention(q, kc, vc, causal=True, lengths=lens,
                                  k_prefix=kp, v_prefix=vp,
                                  prefix_lengths=plens)
        for bi in range(b):              # rows past lens are undefined
            n = int(lens[bi])
            np.testing.assert_allclose(np.asarray(out)[bi, :, :n],
                                       np.asarray(expect)[bi, :, :n],
                                       rtol=3e-5, atol=3e-5)


def _stacked_arenas(rng, layers, pages, ps, kvh, d, dtype=np.float32):
    """Two (layers, pages, ps, kvh, d) arenas: the layout the serving
    engine stacks and the kernel reads in place."""
    shape = (layers, pages, ps, kvh, d)
    return (jnp.asarray(rng.normal(size=shape).astype(dtype)),
            jnp.asarray(rng.normal(size=shape).astype(dtype)))


# float32 arenas: the kernel and the oracle differ only in summation
# order.  bfloat16 arenas: the kernel also rounds the probabilities to
# bfloat16 for the PV product, as the compiled oracle does at the TPU's
# default matmul precision; the oracle here keeps them in float32.
PA_TOL = {np.float32: 3e-5, jnp.bfloat16: 2e-2}


class TestPagedAttention:
    @settings(**SETTINGS)
    @given(b=st.integers(1, 3), kvh=st.sampled_from([1, 2, 4]),
           g=st.sampled_from([1, 2, 4]), ps=st.sampled_from([8, 16]),
           npages=st.integers(2, 6), seed=st.integers(0, 50))
    def test_matches_ref(self, b, kvh, g, ps, npages, seed):
        h = kvh * g
        d = 32
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))
        layers = 3
        ka, va = _stacked_arenas(rng, layers, npages * b + 1, ps, kvh, d)
        layer = int(rng.integers(0, layers))
        bt = jnp.asarray(rng.permutation(npages * b).reshape(b, npages).astype(np.int32))
        lengths = jnp.asarray(rng.integers(0, npages * ps + 1, b).astype(np.int32))
        out = pa.paged_attention(q, ka, va, layer, bt, lengths, interpret=True)
        expect = pa_ref.paged_attention(q, ka, va, layer, bt, lengths)
        np.testing.assert_allclose(out, expect, rtol=3e-5, atol=3e-5)


class TestPagedAttentionFusion:
    """The decode-fusion hooks: LSE-returning variant and the in-kernel
    current-token (self) merge, vs the jnp oracle and a dense oracle,
    over stacked arenas read at a layer index."""

    @staticmethod
    def _setup(seed=0, b=3, kvh=2, g=2, ps=8, npages=4, d=32, layers=3,
               lens=None, dtype=np.float32):
        h = kvh * g
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, h, d)).astype(dtype))
        ka, va = _stacked_arenas(rng, layers, npages * b + 1, ps, kvh, d,
                                 dtype)
        bt = jnp.asarray(rng.permutation(npages * b).reshape(b, npages).astype(np.int32))
        if lens is None:
            lens = rng.integers(1, npages * ps, b)
        lens = jnp.asarray(np.asarray(lens, np.int32))
        ks = jnp.asarray(rng.normal(size=(b, kvh, d)).astype(dtype))
        vs = jnp.asarray(rng.normal(size=(b, kvh, d)).astype(dtype))
        return q, ka, va, bt, lens, ks, vs

    @pytest.mark.parametrize("kvh,g,layer,with_self", [
        (2, 2, 0, False), (2, 4, 2, True), (8, 4, 1, False),
        (8, 4, 2, True)])
    def test_lse_variant_matches_ref(self, kvh, g, layer, with_self):
        for seed in range(3):
            q, ka, va, bt, lens, ks, vs = self._setup(seed, kvh=kvh, g=g)
            kw = dict(k_self=ks, v_self=vs) if with_self else {}
            o1, m1, l1 = pa.paged_attention(q, ka, va, layer, bt, lens,
                                            interpret=True, return_lse=True,
                                            **kw)
            o2, m2, l2 = pa_ref.paged_attention(q, ka, va, layer, bt, lens,
                                                return_lse=True, **kw)
            np.testing.assert_allclose(o1, o2, rtol=3e-5, atol=3e-5)
            np.testing.assert_allclose(m1, m2, rtol=3e-5, atol=3e-5)
            np.testing.assert_allclose(l1, l2, rtol=3e-5, atol=3e-5)

    # pages of 16 tokens in a 16-page table, read in blocks of 64 tokens
    # (set small here, so that a row spans several blocks): 64 ends a
    # block, 65 fetches one page of the next, 256 fills the table
    EDGE_LENS = [0, 1, 15, 16, 17, 64, 65, 256]

    @pytest.mark.parametrize("kvh,lens,pad,dtype", [
        (2, None, 0, np.float32),
        (8, EDGE_LENS, 0, np.float32),
        (2, EDGE_LENS, 0, np.float32),
        # two pad rows repeat row 0, as the engine pads a batch
        (8, [200, 17, 1], 2, np.float32),
        (8, EDGE_LENS, 0, jnp.bfloat16)])
    def test_self_token_merge_matches_ref(self, kvh, lens, pad, dtype,
                                          monkeypatch):
        kw = {}
        if lens:
            monkeypatch.setattr(pa, "_BLOCK_TOKENS", 64)
            kw = dict(ps=16, npages=16, b=len(lens) + pad)
        if pad:
            lens = lens + [lens[0]] * pad
        for seed, layer in ((0, 0), (1, 1), (2, 2)):
            q, ka, va, bt, ln, ks, vs = self._setup(
                seed, kvh=kvh, g=4, lens=lens, dtype=dtype, **kw)
            if pad:
                rows = np.r_[np.arange(len(lens) - pad), np.zeros(pad, int)]
                q, bt, ks, vs = (x[rows] for x in (q, bt, ks, vs))
            out = pa.paged_attention(q, ka, va, layer, bt, ln,
                                     interpret=True, k_self=ks, v_self=vs)
            expect = pa_ref.paged_attention(q, ka, va, layer, bt, ln,
                                            k_self=ks, v_self=vs)
            tol = PA_TOL[dtype]
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(expect, np.float32),
                                       rtol=tol, atol=tol)

    @pytest.mark.parametrize("layer", [0, 2])
    def test_self_token_merge_matches_dense_oracle(self, layer):
        # independent oracle: gather history + append self token, then a
        # plain (non-streaming) softmax per sequence
        b, kvh, g, d = 2, 2, 2, 32
        h = kvh * g
        q, ka, va, bt, lens, ks, vs = self._setup(7, b=b, kvh=kvh, g=g, d=d)
        out = np.asarray(pa.paged_attention(q, ka, va, layer, bt, lens,
                                            interpret=True,
                                            k_self=ks, v_self=vs))
        scale = d ** -0.5
        for i in range(b):
            L = int(lens[i])
            kk = np.asarray(ka[layer][bt[i]]).reshape(-1, kvh, d)[:L]
            vv = np.asarray(va[layer][bt[i]]).reshape(-1, kvh, d)[:L]
            kk = np.concatenate([kk, np.asarray(ks[i])[None]], 0)
            vv = np.concatenate([vv, np.asarray(vs[i])[None]], 0)
            qi = np.asarray(q[i]).reshape(kvh, g, d)
            s = np.einsum("kgd,skd->kgs", qi, kk) * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            oo = np.einsum("kgs,skd->kgd", p, vv).reshape(h, d)
            np.testing.assert_allclose(out[i], oo, rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("lens,width,expect", [
        # pages of 16 tokens: 0+1+1+1+2+4+5+16 pages
        ([0, 1, 15, 16, 17, 64, 65, 256], 16, 30 * 16),
        ([300, 1], 256, (19 + 1) * 16),
        # a length past the table reads the table
        ([5, 300], 4, (1 + 4) * 16)])
    def test_kv_slots_read_counts_the_pages_below_each_length(
            self, lens, width, expect):
        from repro.kernels.paged_attention import ops as pa_ops
        assert pa_ops.kv_slots_read(lens, width, 16, True) == expect
        if jax.default_backend() != "tpu":
            assert pa_ops.kv_slots_read(lens, width, 16, False) == (
                len(lens) * width * 16)
