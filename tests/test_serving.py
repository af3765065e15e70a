"""Serving: paged engine vs dense decode, CoW forking, prefix sharing,
page lifecycle security (pim_init on free), allocator integration."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, ParallelConfig, reduced
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.engine import PagedEngine, Request
from repro.serving.kv_cache import PagedKVCache

PCFG = ParallelConfig(attention_impl="naive", remat="none")


@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def greedy_dense(cfg, params, prompt, new):
    toks = jnp.asarray(prompt)[None]
    n = len(prompt)
    cache = T.init_cache(cfg, 1, n + new + 1)
    lg, cache, _ = T.forward(cfg, PCFG, params, {"tokens": toks},
                             mode="prefill", cache=cache,
                             lengths=jnp.asarray([n], jnp.int32))
    out = [int(jnp.argmax(lg[0, 0]))]
    for t in range(new - 1):
        pos = n + t
        lg, cache = T.forward(cfg, PCFG, params,
                              {"tokens": jnp.asarray([[out[-1]]], jnp.int32)},
                              mode="decode", cache=cache,
                              write_pos=jnp.asarray(pos),
                              lengths=jnp.asarray([pos + 1], jnp.int32))
        out.append(int(jnp.argmax(lg[0, 0])))
    return out


class TestPagedEngine:
    def test_matches_dense_greedy(self, model, rng):
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
        ref = greedy_dense(cfg, params, prompt, 5)
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        eng.submit(Request(0, prompt, max_new_tokens=5, temperature=0.0))
        assert eng.run()[0] == ref

    @pytest.mark.slow
    def test_batched_requests_isolated(self, model, rng):
        cfg, params = model
        p1 = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab_size, 14).astype(np.int32)
        ref1 = greedy_dense(cfg, params, p1, 4)
        ref2 = greedy_dense(cfg, params, p2, 4)
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        eng.submit(Request(0, p1, max_new_tokens=4, temperature=0.0))
        eng.submit(Request(1, p2, max_new_tokens=4, temperature=0.0))
        res = eng.run()
        assert res[0] == ref1 and res[1] == ref2

    def test_prefix_sharing_and_page_accounting(self, model, rng):
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        eng.submit(Request(0, prompt, max_new_tokens=3, temperature=0.0))
        eng.submit(Request(1, prompt, max_new_tokens=3, temperature=0.0,
                           share_with=0, shared_len=12))
        res = eng.run()
        assert res[0] == res[1]
        assert eng.cache.stats["prefix_hits"] == 1
        assert eng.cache.pages_in_use == 0  # everything freed

    def test_pages_zeroed_on_free(self, model, rng):
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        eng = PagedEngine(cfg, params, page_size=4, num_pages=16)
        eng.submit(Request(0, prompt, max_new_tokens=2, temperature=0.0))
        eng.run()
        assert eng.cache.stats["pages_zeroed"] > 0
        # the arena holds no residual data (security property)
        assert float(jnp.abs(eng.cache.k_arena).sum()) == 0.0
        assert float(jnp.abs(eng.cache.v_arena).sum()) == 0.0


class TestKVCacheUnit:
    def test_fork_cow_semantics(self, model):
        cfg, _ = model
        cache = PagedKVCache(cfg, num_pages=32, page_size=4)
        seq = cache.create(0, 10)  # 3 pages (2 full + 1 partial)
        k = jnp.ones((cache.n_layers, cfg.num_kv_heads, cfg.resolved_head_dim))
        forked = cache.fork(0, 1)
        assert cache.stats["cow_copies"] == 1     # partial tail copied
        assert forked.pages[:2] == cache.seqs[0].pages[:2]  # shared
        assert forked.pages[2] != cache.seqs[0].pages[2]    # CoW'd
        # appending to the original does not affect the fork
        cache.append_token_kv(cache.seqs[0], k, k)
        assert cache.seqs[1].length == 10

    def test_same_slab_preference(self, model):
        cfg, _ = model
        cache = PagedKVCache(cfg, num_pages=32, page_size=4, num_slabs=4)
        seq = cache.create(0, 16)
        groups = {cache.page_alloc[p].group for p in seq.pages}
        assert len(groups) == 1  # RowClone-constraint honoured

    def test_out_of_pages_raises(self, model):
        from repro.core.allocator import PimAllocError
        cfg, _ = model
        cache = PagedKVCache(cfg, num_pages=8, page_size=4)
        cache.create(0, 8 * 4)
        with pytest.raises(PimAllocError):
            cache.create(1, 8)


class TestDispatchCounts:
    """Regression: arena mutations cost a CONSTANT number of kernel
    launches, independent of num_layers and active-batch size (the
    batched PiM op scheduler's contract)."""

    @staticmethod
    def _cache(layers, **kw):
        cfg = reduced(ARCHS["granite-3-8b"], num_layers=layers)
        return cfg, PagedKVCache(cfg, num_pages=32, page_size=4, **kw)

    def test_cow_fork_launches_independent_of_layers(self):
        counts = []
        for layers in (1, 2, 4):
            _, cache = self._cache(layers)
            cache.create(0, 10)       # 2 full pages + partial tail
            base = cache.queue.stats["launches"]
            cache.fork(0, 1)
            counts.append(cache.queue.stats["launches"] - base)
        assert len(set(counts)) == 1, counts
        assert counts[0] == 2         # one batched copy per arena (k, v)

    def test_page_free_launches_independent_of_layers_and_size(self):
        counts = []
        for layers, prompt_len in ((1, 6), (2, 6), (4, 6), (2, 26)):
            _, cache = self._cache(layers)
            cache.create(0, prompt_len)
            base = cache.queue.stats["launches"]
            cache.free(0)
            counts.append(cache.queue.stats["launches"] - base)
        # 1..7 dead pages, 1..4 layers -> always one batched init per arena
        assert set(counts) == {2}, counts

    def test_prompt_write_launches_independent_of_length_and_layers(self):
        counts = []
        for layers, n in ((1, 3), (2, 9), (4, 14)):
            cfg, cache = self._cache(layers)
            seq = cache.create(0, n)
            k = jnp.ones((cache.n_layers, n, cfg.num_kv_heads,
                          cfg.resolved_head_dim))
            base = cache.queue.stats["launches"]
            cache.write_prompt_kv(seq, k, k)
            counts.append(cache.queue.stats["launches"] - base)
        assert set(counts) == {2}, counts   # one KV scatter per arena

    @staticmethod
    def _decode_round_launches(layers, nreqs, rng):
        cfg = reduced(ARCHS["granite-3-8b"], num_layers=layers)
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        for i in range(nreqs):
            prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=4, temperature=0.0))
        while eng.queue:
            eng._prefill(eng.queue.pop(0))
        base = eng.cache.queue.stats["launches"]
        eng._decode_round()
        return eng.cache.queue.stats["launches"] - base

    def test_decode_round_launches_independent_of_layers_and_batch(self, rng):
        a = self._decode_round_launches(1, 1, rng)
        b = self._decode_round_launches(2, 3, rng)
        assert a == b, (a, b)
        # the fused round is ONE dispatch (forward + scatter + sampling
        # in a single jit); a CoW flush would add two more when forking
        assert b <= 2

    def test_full_prefix_hit_writes_nothing(self):
        # a prompt fully covered by a shared prefix enqueues an empty KV
        # batch -> no launch, no flush, counters stay truthful
        cfg, cache = self._cache(2)
        seq0 = cache.create(0, 8)
        k = jnp.ones((cache.n_layers, 8, cfg.num_kv_heads,
                      cfg.resolved_head_dim))
        cache.write_prompt_kv(seq0, k, k)
        cache.create(1, 8, share_with=0, shared_len=8)
        base = dict(cache.queue.stats)
        cache.write_prompt_kv(cache.seqs[1], k[:, 8:], k[:, 8:], start=8)
        assert cache.queue.stats == base

    def test_queue_coalesces_ops(self):
        _, cache = self._cache(2)
        cache.create(0, 26)           # 7 pages
        cache.free(0)
        q = cache.queue.stats
        assert q["ops_enqueued"] == 7                 # 7 page inits...
        assert cache.queue.launches_by_kind["page_init"] == 2  # ...2 launches

    @staticmethod
    def _fused_prefill_launches(layers, nreqs, prompt_len, rng):
        cfg = reduced(ARCHS["granite-3-8b"], num_layers=layers)
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        for i in range(nreqs):
            prompt = rng.integers(0, cfg.vocab_size, prompt_len)
            eng.submit(Request(i, prompt.astype(np.int32), max_new_tokens=1,
                               temperature=0.0))
        base = eng.cache.queue.stats["launches"]
        eng._prefill_round()
        assert eng.cache.queue.launches_by_kind["fused_prefill"] == 1
        return eng.cache.queue.stats["launches"] - base

    def test_fused_prefill_launches_independent_of_layers_and_batch(self, rng):
        """A same-bucket prefill batch is ONE dispatch (forward + KV
        scatter + sampling in a single jit, accounted as the
        ``fused_prefill`` kind) no matter how many layers the model has,
        how many requests stack into the batch, or how long the prompts
        are."""
        counts = [self._fused_prefill_launches(layers, nreqs, plen, rng)
                  for layers, nreqs, plen in
                  ((1, 1, 7), (2, 3, 7), (4, 2, 14))]
        assert set(counts) == {1}, counts

    def test_chunked_prefill_chunks_account_as_fused_prefill(self, rng):
        """Every chunk batch is ONE dispatch accounted under the same
        ``fused_prefill`` kind as monolithic batches — a 3-chunk prompt
        shows 3 fused_prefill launches and nothing else, independent of
        layer count."""
        for layers in (1, 2):
            cfg = reduced(ARCHS["granite-3-8b"], num_layers=layers)
            params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
            eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                              max_prefill_chunk=8)
            prompt = rng.integers(0, cfg.vocab_size, 22).astype(np.int32)
            eng.submit(Request(0, prompt, max_new_tokens=1, temperature=0.0))
            base = eng.cache.queue.stats["launches"]
            base_kind = eng.cache.queue.launches_by_kind.get("fused_prefill", 0)
            while eng.queue or eng._chunk_q:
                eng._prefill_tick()
            assert (eng.cache.queue.launches_by_kind["fused_prefill"]
                    - base_kind == 3)
            assert eng.cache.queue.stats["launches"] - base == 3
            assert eng.stats["prefill_chunks"] == 3

    def test_k_block_decode_under_one_dispatch_per_token(self, rng):
        """Dispatches-per-token regression for the persistent decode
        loop: after warmup, a 32-round pure-decode workload at K=8 folds
        every 8 rounds into ONE ``fused_decode_block`` launch — 4
        dispatches for 64 tokens, well under 1 per token."""
        cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
        eng = PagedEngine(cfg, params, page_size=4, num_pages=128,
                          decode_block_rounds=8)
        nreqs = 2
        for i in range(nreqs):
            prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=64,
                               temperature=0.0))
        eng.run(max_rounds=9)           # warmup: prefill + first block
        assert len(eng.active) == nreqs
        before = eng.cache.queue.snapshot()
        base_tokens = eng.stats["tokens_out"]
        eng.run(max_rounds=32)          # pure decode, nothing queued
        delta = eng.cache.queue.delta(before)
        tokens = eng.stats["tokens_out"] - base_tokens
        assert delta == {"fused_decode_block": 4}, delta
        assert tokens == 32 * nreqs
        dispatches_per_token = sum(delta.values()) / tokens
        assert dispatches_per_token < 1.0
        assert eng.stats["multi_round_blocks"] >= 5

    def test_mixed_round_is_exactly_one_dispatch(self, rng):
        """A round running a chunk batch AND the decode round costs
        exactly ONE launch (the ``fused_mixed`` kind) — and the chunked
        scheduler keeps ``decode_stall_rounds`` at 0 throughout."""
        cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                          max_prefill_chunk=8)
        prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
        eng.submit(Request(0, prompt, max_new_tokens=32, temperature=0.0))
        eng.run(max_rounds=2)           # request 0 is now mid-decode
        assert sorted(eng.active) == [0]
        # a long prompt arrives: its chunk rides the decode dispatch
        long_prompt = rng.integers(0, cfg.vocab_size, 22).astype(np.int32)
        eng.submit(Request(1, long_prompt, max_new_tokens=4,
                           temperature=0.0))
        before = eng.cache.queue.snapshot()
        base_mixed = eng.stats["mixed_dispatches"]
        eng.run(max_rounds=1)
        delta = eng.cache.queue.delta(before)
        assert delta == {"fused_mixed": 1}, delta
        assert eng.stats["mixed_dispatches"] == base_mixed + 1
        assert eng.stats["decode_stall_rounds"] == 0

    @staticmethod
    def _state_engine(arch, rng, *, nreqs=2, budget=4, **kw):
        over = {k: kw.pop(k) for k in ("num_layers", "attn_every")
                if k in kw}
        cfg = reduced(ARCHS[arch], **over)
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(1))
        eng = PagedEngine(cfg, params, page_size=4, num_pages=128, **kw)
        for i in range(nreqs):
            prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=budget,
                               temperature=0.0))
        return eng

    def test_hybrid_decode_round_is_one_dispatch(self, rng):
        """A hybrid decode round stays ONE dispatch: the in-scan state
        scatter and in-jit MoE routing ride the fused step, so no
        ``ssm_state_write`` (or any other) launch appears next to the
        single ``fused_decode``."""
        for arch, kw in (("mamba2-1.3b", dict(num_layers=2)),
                         ("jamba-1.5-large-398b",
                          dict(num_layers=4, attn_every=4))):
            eng = self._state_engine(arch, rng, **kw)
            while eng.queue:
                eng._prefill(eng.queue.pop(0))
            eng.cache.flush_pending()
            before = eng.cache.queue.snapshot()
            eng._decode_round()
            delta = eng.cache.queue.delta(before)
            assert delta == {"fused_decode": 1}, (arch, delta)

    def test_eager_state_write_launches_constant_in_layers_and_batch(
            self, rng):
        """The eager oracle pays the ``SSM_STATE_WRITE`` opcode's real
        price — and that price is one coalesced flush per round (2
        launches: conv + ssm arena), independent of depth and batch."""
        counts = []
        for layers, nreqs in ((1, 1), (2, 3)):
            eng = self._state_engine("mamba2-1.3b", rng, nreqs=nreqs,
                                     num_layers=layers, fused=False,
                                     fused_prefill=False)
            while eng.queue:
                eng._prefill(eng.queue.pop(0))
            eng.cache.flush_pending()
            before = eng.cache.queue.snapshot()
            eng._decode_round()
            counts.append(eng.cache.queue.delta(before)["ssm_state_write"])
        assert set(counts) == {2}, counts

    def test_k_block_hybrid_decode_under_one_dispatch_per_token(self, rng):
        """The persistent decode loop holds its dispatches-per-token win
        on state-arena layouts: 16 pure-decode rounds at K=8 fold into 2
        ``fused_decode_block`` launches."""
        eng = self._state_engine("mamba2-1.3b", rng, num_layers=2,
                                 budget=48, decode_block_rounds=8)
        eng.run(max_rounds=9)           # warmup: prefills + first block
        assert len(eng.active) == 2
        before = eng.cache.queue.snapshot()
        base_tokens = eng.stats["tokens_out"]
        eng.run(max_rounds=16)          # pure decode, nothing queued
        delta = eng.cache.queue.delta(before)
        tokens = eng.stats["tokens_out"] - base_tokens
        assert delta == {"fused_decode_block": 2}, delta
        assert tokens == 16 * 2
        assert sum(delta.values()) / tokens < 1.0


class TestFusedDecode:
    """The fused single-dispatch decode round: jitted scan-over-layers
    with in-kernel self-token merge and in-jit scatter + sampling."""

    def test_fused_matches_eager_tokens(self, model, rng):
        cfg, params = model
        p1 = rng.integers(0, cfg.vocab_size, 10).astype(np.int32)
        p2 = rng.integers(0, cfg.vocab_size, 13).astype(np.int32)
        outs = []
        for fused in (True, False):
            eng = PagedEngine(cfg, params, page_size=4, num_pages=64,
                              fused=fused)
            eng.submit(Request(0, p1, max_new_tokens=4, temperature=0.0))
            eng.submit(Request(1, p2, max_new_tokens=4, temperature=0.0))
            res = eng.run()
            outs.append((tuple(res[0]), tuple(res[1])))
        assert outs[0] == outs[1]

    # use_pallas: both forwards take the paged kernel (interpreted
    # here), which reads the stacked arena at the scan's layer index
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_scan_forward_matches_eager_logits(self, model, rng,
                                               use_pallas):
        from repro.serving import engine as E
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        for i, n in enumerate((9, 14)):
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=4, temperature=0.0))
        while eng.queue:
            eng._prefill(eng.queue.pop(0))
        rids = sorted(eng.active)
        for r in rids:
            eng.cache.ensure_writable_tail(eng.cache.seqs[r])
        eng.cache.flush_pending()
        last = jnp.asarray([[eng.active[r].out_tokens[-1]] for r in rids],
                           jnp.int32)
        bt, lens = eng.cache.block_table(rids)
        args = (cfg, eng.pcfg, params, last, eng.cache.k_arena,
                eng.cache.v_arena, bt, lens)
        lg_s, k_s, v_s, _, _ = E._paged_decode_forward(
            *args, use_pallas=use_pallas, interpret=True)
        lg_e, k_e, v_e, _, _ = E._eager_decode_forward(
            *args, use_pallas=use_pallas, interpret=True)
        # fp32 logits over bf16 activations: scan vs unrolled loops may
        # fuse/round differently, so parity holds at bf16 resolution
        np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_e),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(k_s, np.float32),
                                   np.asarray(k_e, np.float32),
                                   rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(np.asarray(v_s, np.float32),
                                   np.asarray(v_e, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_recompilation_bounded_over_growing_rounds(self, model, rng):
        """20 decode rounds with growing sequences and a mid-flight
        arrival: block-table/batch bucketing keeps jit retraces at
        power-of-two boundaries, not one per round."""
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=128)
        p0 = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        eng.submit(Request(0, p0, max_new_tokens=30, temperature=0.0))
        while eng.queue:
            eng._prefill(eng.queue.pop(0))
        for _ in range(8):
            eng._decode_round()
        # a second request joins between rounds (batch grows / "forks")
        p1 = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
        eng.submit(Request(1, p1, max_new_tokens=30, temperature=0.0))
        while eng.queue:
            eng._prefill(eng.queue.pop(0))
        for _ in range(7):
            eng._decode_round()
        traces_mid = eng.stats["jit_traces"]
        for _ in range(5):
            eng._decode_round()
        assert eng.stats["decode_rounds"] == 20
        assert eng.stats["jit_traces"] <= 5, eng.stats
        # steady state: page/batch buckets stable -> no further retraces
        assert eng.stats["jit_traces"] == traces_mid

    def test_fused_round_is_one_dispatch_after_warmup(self, model, rng):
        cfg, params = model
        eng = PagedEngine(cfg, params, page_size=4, num_pages=64)
        for i in range(2):
            prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)
            eng.submit(Request(i, prompt, max_new_tokens=6, temperature=0.0))
        while eng.queue:
            eng._prefill(eng.queue.pop(0))
        eng._decode_round()                      # warmup (traces)
        base = eng.cache.queue.stats["launches"]
        eng._decode_round()
        assert eng.cache.queue.stats["launches"] - base == 1
        assert eng.cache.queue.launches_by_kind["fused_decode"] == 2


class TestSampling:
    def test_temperature_zero_deterministic(self, model, rng):
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        outs = []
        for _ in range(2):
            eng = PagedEngine(cfg, params, page_size=4, num_pages=32)
            eng.submit(Request(0, prompt, max_new_tokens=4, temperature=0.0))
            outs.append(tuple(eng.run()[0]))
        assert outs[0] == outs[1]

    def test_sampled_tokens_vary_with_seed(self, model, rng):
        cfg, params = model
        prompt = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
        outs = set()
        for seed in range(3):
            eng = PagedEngine(cfg, params, page_size=4, num_pages=32, seed=seed)
            eng.submit(Request(0, prompt, max_new_tokens=6, temperature=2.0))
            outs.add(tuple(eng.run()[0]))
        assert len(outs) > 1
