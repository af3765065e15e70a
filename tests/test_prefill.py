"""Fused bucketed prefill: one jitted dispatch per prefill batch.

The parity harness: ``fused_prefill=False`` keeps the eager
per-request prefill (un-jitted dense ``T.forward`` + host-side
``write_prompt_kv``) as the oracle, so the fused path is pinned by
fused-vs-eager **token**, **logit**, and **arena-content** parity —
across prompt lengths that straddle power-of-two bucket boundaries,
shared-prefix (``share_with``) requests, and mixed-length batches —
plus retrace regressions on ``stats["prefill_jit_traces"]``.
(The dispatch-count regressions live with the other launch-count pins
in ``tests/test_serving.py::TestDispatchCounts``.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ParallelConfig, reduced
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.engine import PagedEngine, Request
from repro.serving.kv_cache import _bucket_pow2

PCFG = ParallelConfig(attention_impl="naive", remat="none")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _engine_pair(cfg, params, **kw):
    fused = PagedEngine(cfg, params, page_size=4, num_pages=128,
                        fused_prefill=True, **kw)
    eager = PagedEngine(cfg, params, page_size=4, num_pages=128,
                        fused_prefill=False, **kw)
    return fused, eager


def _submit_all(engines, reqs):
    """Submit fresh Request copies to every engine (Requests mutate)."""
    for eng in engines:
        for r in reqs:
            eng.submit(Request(r.req_id, r.prompt,
                               max_new_tokens=r.max_new_tokens,
                               temperature=r.temperature,
                               share_with=r.share_with,
                               shared_len=r.shared_len))


def _arenas_equal(a, b):
    # both paths compute K/V in bf16; scan-vs-dense fusion may round
    # intermediates differently, so parity holds at bf16 resolution
    np.testing.assert_allclose(
        np.asarray(a.cache.k_arena, np.float32),
        np.asarray(b.cache.k_arena, np.float32), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        np.asarray(a.cache.v_arena, np.float32),
        np.asarray(b.cache.v_arena, np.float32), rtol=2e-2, atol=2e-2)


class TestPrefillParity:
    def test_bucket_boundary_lengths(self, model):
        """7/8/9 and 15/16/17 straddle the 8- and 16-buckets: each
        prompt prefills as its own batch (separate rounds) and must
        match the eager oracle token-for-token, with identical arena
        contents after the prefill writes."""
        # prompts from a generator of the test's own: the session ``rng``
        # made them depend on which test files ran first in the worker,
        # and the arena check is at bf16 resolution, so one draw in
        # about six lands an element just past it
        rng = np.random.default_rng(1)
        cfg, params = model
        fused, eager = _engine_pair(cfg, params)
        for i, n in enumerate((7, 8, 9, 15, 16, 17)):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            req = Request(i, prompt, max_new_tokens=2, temperature=0.0)
            _submit_all((fused, eager), [req])
            fused._prefill_round()
            eager._prefill_round()
            _arenas_equal(fused, eager)   # prompt KV written identically
            f = fused.active[i].out_tokens
            e = eager.active[i].out_tokens
            assert f == e, (n, f, e)
        # and the decode rounds that follow agree too
        assert fused.run() == eager.run()

    def test_mixed_length_batch_parity(self, model, rng):
        """One submission spanning three buckets: the fused path stacks
        per-bucket batches (2, 3, and 1 requests) and must match the
        eager oracle exactly."""
        cfg, params = model
        fused, eager = _engine_pair(cfg, params)
        reqs = [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                        max_new_tokens=3, temperature=0.0)
                for i, n in enumerate((7, 8, 9, 15, 16, 17))]
        _submit_all((fused, eager), reqs)
        res_f, res_e = fused.run(), eager.run()
        assert res_f == res_e
        assert fused.stats["prefills"] == 6
        # 3 distinct (length-bucket, batch-bucket) pairs -> 3 traces
        assert fused.stats["prefill_jit_traces"] == 3

    def test_shared_prefix_parity(self, model, rng):
        """`share_with` requests skip the shared pages in the scatter
        plan; fused and eager must agree on tokens, arena contents, and
        prefix accounting — including a sharer whose prompt is FULLY
        covered by the prefix (the all-no-op scatter batch)."""
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        fused, eager = _engine_pair(cfg, params)
        reqs = [Request(0, prompt, max_new_tokens=3, temperature=0.0),
                Request(1, prompt, max_new_tokens=3, temperature=0.0,
                        share_with=0, shared_len=12)]
        _submit_all((fused, eager), reqs)
        fused._prefill_round()
        eager._prefill_round()
        _arenas_equal(fused, eager)
        # a fully-covered sharer arrives next round: nothing to write,
        # and the no-op scatter must leave the arena untouched
        before = np.asarray(fused.cache.k_arena, np.float32).copy()
        _submit_all((fused, eager),
                    [Request(2, prompt, max_new_tokens=3, temperature=0.0,
                             share_with=0, shared_len=16)])
        fused._prefill_round()
        eager._prefill_round()
        np.testing.assert_array_equal(
            before, np.asarray(fused.cache.k_arena, np.float32))
        _arenas_equal(fused, eager)
        res_f, res_e = fused.run(), eager.run()
        assert res_f == res_e
        assert res_f[0] == res_f[1] == res_f[2]
        assert fused.cache.stats["prefix_hits"] == 2
        assert (fused.cache.stats["prefix_hits"]
                == eager.cache.stats["prefix_hits"])

    @pytest.mark.parametrize("path", ["fused", "chunked", "eager"])
    def test_keep_logits_holds_first_token_logits(self, model, path):
        """``Request(keep_logits=True)`` keeps the (vocab,) logits its
        first token was chosen from, on every prefill path, at bf16
        resolution of the eager prefill's; a request that does not ask
        keeps none."""
        cfg, params = model
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 11, 6)]
        kw = {"fused": {}, "chunked": {"max_prefill_chunk": 4},
              "eager": {"fused_prefill": False}}

        def serve(**engine_kw):
            eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                              **engine_kw)
            reqs = [Request(i, p, max_new_tokens=2, temperature=0.0,
                            keep_logits=i < 2)
                    for i, p in enumerate(prompts)]
            for r in reqs:
                eng.submit(r)
            eng.run()
            return reqs

        oracle = serve(**kw["eager"])
        for r, o in zip(serve(**kw[path]), oracle):
            if not r.keep_logits:
                assert r.logits is None
                continue
            lg = np.asarray(r.logits, np.float32)
            assert lg.shape == (cfg.vocab_size,)
            assert int(np.argmax(lg)) == r.out_tokens[0]
            np.testing.assert_allclose(lg, np.asarray(o.logits, np.float32),
                                       rtol=2e-2, atol=2e-2)

    def test_prefill_forward_matches_eager_logits(self, model, rng):
        """Logit-level parity of the scan/masked forward against the
        dense ``T.forward`` oracle, at bf16 resolution, for a padded
        (bucketed) and an exact-fit prompt — plus the stacked K/V the
        scatter plan sources."""
        from repro.serving import engine as E
        cfg, params = model
        for n in (5, 8):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            sp = _bucket_pow2(n)
            toks = np.zeros((1, sp), np.int32)
            toks[0, :n] = prompt
            lg_f, k_all, v_all, _, _ = E._prefill_forward(
                cfg, PCFG, params, jnp.asarray(toks),
                jnp.asarray([n], jnp.int32), use_pallas=False,
                interpret=True)
            cache = T.init_cache(cfg, 1, n)
            lg_e, dense, _ = T.forward(
                cfg, PCFG, params, {"tokens": jnp.asarray(prompt)[None]},
                mode="prefill", cache=cache,
                lengths=jnp.asarray([n], jnp.int32))
            np.testing.assert_allclose(np.asarray(lg_f[0]),
                                       np.asarray(lg_e[0, 0]),
                                       rtol=2e-2, atol=2e-2)
            k_e, v_e = dense["group0"]["0_attn"]   # (L, 1, n, kvh, hd)
            np.testing.assert_allclose(
                np.asarray(k_all[:, 0, :n], np.float32),
                np.asarray(k_e[:, 0], np.float32), rtol=2e-2, atol=2e-2)
            np.testing.assert_allclose(
                np.asarray(v_all[:, 0, :n], np.float32),
                np.asarray(v_e[:, 0], np.float32), rtol=2e-2, atol=2e-2)

    def test_pallas_path_matches_reference(self, model, rng):
        """The length-masked Pallas flash kernel drives the same fused
        prefill to the same tokens as the jnp reference path."""
        cfg, params = model
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 7)]
        outs = []
        for use_pallas in (False, True):
            # on the CPU backend the kernels run interpreted
            eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                              use_pallas=use_pallas)
            for i, p in enumerate(prompts):
                eng.submit(Request(i, p, max_new_tokens=2, temperature=0.0))
            outs.append(eng.run())
        assert outs[0] == outs[1]


class TestChunkedPrefill:
    """Chunked prefill with decode-interleaved scheduling
    (``max_prefill_chunk``): prompts longer than one chunk stream across
    rounds through the prefix-KV flash path, pinned against the same
    eager oracle as the monolithic fused prefill."""

    CHUNK = 8

    def _pair(self, cfg, params, **kw):
        chunked = PagedEngine(cfg, params, page_size=4, num_pages=128,
                              max_prefill_chunk=self.CHUNK, **kw)
        eager = PagedEngine(cfg, params, page_size=4, num_pages=128,
                            fused_prefill=False, **kw)
        return chunked, eager

    @staticmethod
    def _drain_prefill(eng):
        """Run prefill ticks (no decode) until nothing is mid-prefill."""
        while eng.queue or eng._chunk_q:
            eng._prefill_tick()

    def test_chunk_straddling_lengths_match_eager(self, model, rng):
        """7/9/17/23/32 with an 8-token chunk cover: single sub-chunk
        prompts, chunk-exact prompts, and 2-4 chunk prompts with ragged
        tails.  Token AND arena parity against the eager oracle after
        every prompt's prefill, then decode-round parity."""
        cfg, params = model
        chunked, eager = self._pair(cfg, params)
        for i, n in enumerate((7, 9, 17, 23, 32)):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            req = Request(i, prompt, max_new_tokens=2, temperature=0.0)
            _submit_all((chunked, eager), [req])
            self._drain_prefill(chunked)
            eager._prefill_round()
            _arenas_equal(chunked, eager)   # chunk KV committed identically
            assert (chunked.active[i].out_tokens
                    == eager.active[i].out_tokens), n
        assert chunked.run() == eager.run()
        # 5 prompts, chunk cover of ceil(n/8) each: 1+2+3+3+4
        assert chunked.stats["prefill_chunks"] == 13
        assert chunked.stats["decode_stall_rounds"] == 0

    def test_shared_prefix_composes_with_chunking(self, model, rng):
        """A chunked source plus a partially-covered and a fully-covered
        sharer: the sharers' chunk/first-token work is gated until the
        source commits the shared pages, and results match the eager
        oracle (which prefills everything before any decode)."""
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        chunked, eager = self._pair(cfg, params)
        reqs = [Request(0, prompt, max_new_tokens=3, temperature=0.0),
                Request(1, prompt, max_new_tokens=3, temperature=0.0,
                        share_with=0, shared_len=12),
                Request(2, prompt, max_new_tokens=3, temperature=0.0,
                        share_with=0, shared_len=16)]
        _submit_all((chunked, eager), reqs)
        res_c, res_e = chunked.run(), eager.run()
        assert res_c == res_e
        assert res_c[0] == res_c[1] == res_c[2]
        assert (chunked.cache.stats["prefix_hits"]
                == eager.cache.stats["prefix_hits"] == 2)
        # a fully-covered sharer is ONE no-write chunk — even arriving
        # while its source decodes, it never busts the round budget
        # (the whole-prompt forward a covered sharer used to trigger
        # would stall every in-flight decode behind it)
        assert chunked.stats["decode_stall_rounds"] == 0
        assert chunked.stats["prefill_chunks"] == 2 + 1 + 1  # 16tok,4tok,1tok

    def test_chunk_forward_matches_dense_logits(self, model, rng):
        """Logit-level parity of the prefix-KV chunk forward against the
        dense full-prompt oracle: after chunk 1 commits, chunk 2's
        last-token logits must match ``T.forward`` over the whole prompt
        at that position (bf16 resolution), and its fresh K/V must match
        the dense cache slice the scatter plan would write."""
        from repro.serving import engine as E
        cfg, params = model
        n, c = 12, self.CHUNK
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                          max_prefill_chunk=c)
        eng.submit(Request(0, prompt, max_new_tokens=1, temperature=0.0))
        eng._prefill_tick()              # chunk 1: positions [0, 8)
        seq = eng.cache.seqs[0]
        clen = n - c                     # chunk 2: positions [8, 12)
        toks = np.zeros((1, clen), np.int32)
        toks[0] = prompt[c:]
        bt, plens = eng.cache.block_table([0], lengths=[c])
        lg_c, k_all, v_all, _, _ = E._chunk_prefill_forward(
            cfg, PCFG, params, jnp.asarray(toks),
            jnp.asarray([clen], jnp.int32), jnp.asarray([c], jnp.int32),
            eng.cache.k_arena, eng.cache.v_arena, bt, plens,
            use_pallas=False, interpret=True)
        cache = T.init_cache(cfg, 1, n)
        lg_e, dense, _ = T.forward(
            cfg, PCFG, params, {"tokens": jnp.asarray(prompt)[None]},
            mode="prefill", cache=cache, lengths=jnp.asarray([n], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg_c[0]),
                                   np.asarray(lg_e[0, -1]),
                                   rtol=2e-2, atol=2e-2)
        k_e, v_e = dense["group0"]["0_attn"]   # (L, 1, n, kvh, hd)
        np.testing.assert_allclose(
            np.asarray(k_all[:, 0], np.float32),
            np.asarray(k_e[:, 0, c:], np.float32), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(v_all[:, 0], np.float32),
            np.asarray(v_e[:, 0, c:], np.float32), rtol=2e-2, atol=2e-2)

    def test_decode_emits_every_round_during_long_prefill(self, model, rng):
        """The starvation regression: with a decode in flight, a 4-chunk
        prompt streams across rounds and the decode request still emits
        exactly one token per round; ``decode_stall_rounds`` stays 0.
        The eager oracle fed the same workload (whole-prompt prefill)
        records the stall the chunked scheduler removes."""
        cfg, params = model
        short = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
        long = rng.integers(0, cfg.vocab_size, 4 * self.CHUNK).astype(np.int32)

        def feed(eng):
            eng.submit(Request(0, short.copy(), max_new_tokens=12,
                               temperature=0.0))
            eng.run(max_rounds=2)        # prefill + first decode round
            eng.submit(Request(1, long.copy(), max_new_tokens=2,
                               temperature=0.0))

        chunked = PagedEngine(cfg, params, page_size=4, num_pages=256,
                              max_prefill_chunk=self.CHUNK)
        feed(chunked)
        base_chunks = chunked.stats["prefill_chunks"]   # the short prompt
        deltas = []
        while chunked.queue or chunked.active or chunked._chunk_q:
            before = (len(chunked.active[0].out_tokens)
                      if 0 in chunked.active else None)
            chunked.run(max_rounds=1)
            if before is not None and 0 in chunked.active:
                deltas.append(len(chunked.active[0].out_tokens) - before)
        assert deltas and all(d == 1 for d in deltas), deltas
        assert chunked.stats["prefill_chunks"] - base_chunks == 4
        assert chunked.stats["decode_stall_rounds"] == 0
        # same workload, un-chunked prefill: the decode stalled behind it
        eager = PagedEngine(cfg, params, page_size=4, num_pages=256,
                            fused_prefill=False,
                            max_prefill_chunk=self.CHUNK)
        feed(eager)
        eager.run()
        assert eager.stats["decode_stall_rounds"] >= 1

    def test_no_new_trace_per_chunk_count(self, model, rng):
        """Chunk batches retrace per distinct (chunk-bucket, batch-bucket,
        table-width) triple, never per chunk count: a 17-token prompt
        (3 chunks) and a 25-token prompt (4 chunks) share every bucket,
        so the second compiles NOTHING new — and neither does a rerun."""
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=256,
                          max_prefill_chunk=self.CHUNK)
        traces = []
        for i, n in enumerate((17, 25, 17)):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=1, temperature=0.0))
            TestChunkedPrefill._drain_prefill(eng)
            traces.append(eng.stats["prefill_jit_traces"])
        # full chunks (bucket 8) + ragged tail (bucket 1) compile once;
        # more chunks of the same shape never compile again
        assert traces[0] == traces[1] == traces[2], traces
        eng.run()      # drain so the arena frees cleanly

    def test_pallas_path_matches_reference(self, model, rng):
        """The Pallas prefix-KV flash kernel drives chunked prefill to
        the same tokens as the jnp reference path."""
        cfg, params = model
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (7, 19)]
        outs = []
        for use_pallas in (False, True):
            eng = PagedEngine(cfg, params, page_size=4, num_pages=128,
                              max_prefill_chunk=self.CHUNK,
                              use_pallas=use_pallas)
            for i, p in enumerate(prompts):
                eng.submit(Request(i, p, max_new_tokens=2, temperature=0.0))
            outs.append(eng.run())
        assert outs[0] == outs[1]


class TestPrefillRetrace:
    def test_traces_bounded_by_distinct_buckets(self, model, rng):
        """N prompts of varied lengths compile at most one trace per
        distinct (length-bucket, batch-bucket) pair — and resubmitting
        the same pattern compiles nothing new."""
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=128)

        def burst(base):
            # lengths 5..8 share the 8-bucket (batch of 4); 9 and 12
            # share the 16-bucket (batch of 2, padded to 2)
            for j, n in enumerate((5, 6, 7, 8, 9, 12)):
                prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                eng.submit(Request(base + j, prompt, max_new_tokens=1,
                                   temperature=0.0))
            eng._prefill_round()

        burst(0)
        assert eng.stats["prefill_jit_traces"] == 2
        burst(10)      # identical bucket pattern -> trace cache hits only
        assert eng.stats["prefill_jit_traces"] == 2
        assert eng.stats["fused_prefill_dispatches"] == 4
        eng.run()      # drain so the arena frees cleanly

    def test_single_request_growth_retraces_at_boundaries(self, model, rng):
        """Submitting lengths 7, 8 (same bucket) then 9 (next bucket)
        one at a time: only the bucket crossing retraces."""
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=128)
        traces = []
        for i, n in enumerate((7, 8, 9)):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=1, temperature=0.0))
            eng._prefill_round()
            traces.append(eng.stats["prefill_jit_traces"])
        assert traces == [1, 1, 2], traces
        eng.run()
