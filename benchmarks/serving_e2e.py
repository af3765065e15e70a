"""End-to-end serving comparison (paper's system-level claim, transposed
to the TPU framework), eight tables:

1. RowClone-backed paged KV management (CoW fork + prefix sharing +
   pim_init page recycling) vs a naive engine that re-prefills shared
   prefixes — the paper's copy/init table at the system level.

2. Fused single-dispatch decode round (jitted scan-over-layers,
   in-kernel self-token merge, in-jit scatter + sampling) vs the
   pre-fusion eager layer loop: decode tokens/s, kernel dispatches per
   round, and jit retrace counts.

3. Fused bucketed prefill (one jitted dispatch per length-bucket batch,
   length-masked flash attention, in-jit KV scatter) vs the eager
   per-request path (un-jitted ``T.forward`` per prompt): prefill
   tokens/s, time-to-first-token for the batch, and prefill jit traces.

4. Chunked prefill under mixed traffic: a long prompt arrives while
   short requests are mid-decode.  Chunked (``max_prefill_chunk``)
   streams the prompt across rounds so decodes keep emitting; the
   monolithic engine makes them wait behind the whole prefill.  Reports
   the long prompt's TTFT and the in-flight decodes' p99 inter-token
   latency for both schedulers.

5. Multi-round decode blocking (``decode_block_rounds``): tokens/s and
   dispatches-per-token for K ∈ {1, 4, 8}.  K=1 is one fused dispatch
   per round; K>1 runs up to K decode rounds inside one jitted
   ``lax.while_loop`` dispatch, so dispatches-per-token drops below 1.
   Dispatch counts come from ``PimOpQueue.snapshot()``/``delta()`` —
   the same source of truth the regression tests pin.

6. Tensor-parallel sharded serving: mesh {1, 2, 4} × logit collective
   {psum, psum_compressed} → decode tokens/s, batch TTFT, dispatches
   per round (still ONE — the shard_map program spans all shards), and
   the per-shard ``launches_by_owner`` breakdown.  Every cell runs in
   this process on its own devices; a CPU run gets mesh>1 devices from
   ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (XLA host
   collectives spin-wait and deadlock on boxes under 4 cores).

7. Open-system saturation sweep: Poisson arrivals at >= 3 rates through
   the async front door (``repro.serving.server.AsyncServer``) on a
   shared-system-prompt trace.  Per rate: goodput-under-SLO (requests
   admitted, completed, AND inside their deadline, per second), shed
   fraction, TTFT/ITL p99s, the radix prefix cache's token hit-rate,
   and the recorded trace replayed into RowClone-vs-CPU savings
   (``replay_on_device``) — the open-loop numbers table 4's closed-loop
   scenario cannot show.

8. Ambit zero-compare serving account: the multi-tenant shared-prefix
   workload with ``PagedKVCache.enable_zero_scan()`` on — sequence
   frees zero-scan their dying pages (already-zero tails skip their
   init launch), the prefix-cache teardown audits the init-on-free
   invariant in-arena, and the recorded trace replays on the
   cycle-accurate DDR3 twin (tRAS-corrected precharges + periodic
   refresh, zero scans priced as Ambit TRA OR-reduce sequences):
   RowClone+Ambit vs all-CPU end-to-end totals.

9. Paged hybrid serving (jamba-style: mamba + attention + MoE layers in
   one stack): the paper-scale 100k-token-prompt scenario, clipped to
   the CPU host, streams through the chunked scheduler while short
   requests decode in flight.  Reports serving tokens/s, the decode
   round's dispatch count (ONE ``fused_decode`` — the SSM state scatter
   and MoE routing ride the same jit), and the recorded trace replayed
   into state-arena RowClone savings: copy-on-fork rows as batched
   RowClone copies, init-on-free rows as RowClone-Init, the
   slot-granular ``SSM_STATE_WRITE`` stream priced as CPU traffic on
   both accounts (the capability fallback the model face reports).

Metrics print as ``name,us_per_call,derived`` CSV and the fusion numbers
are also written to ``BENCH_serving.json`` so CI tracks them per PR.
Pass ``--smoke`` for the CI-sized configuration.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.engine import PagedEngine, Request

# anchored to the repo root so the tracked snapshot updates no matter
# which directory the benchmark runs from; smoke runs write a separate
# file so the CI-sized numbers never overwrite the full-config snapshot
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(_ROOT, "BENCH_serving.json")
BENCH_JSON_SMOKE = os.path.join(_ROOT, "BENCH_serving.smoke.json")


def _decode_throughput(cfg, params, rng, *, fused: bool, n_reqs: int,
                       prompt_len: int, new_tokens: int, page_size: int):
    """Decode tokens/s + dispatches/round for one engine mode.

    Warmup batch first (pays jit traces), then a timed batch on the same
    engine: a dispatch-count probe over two mid-flight rounds, then the
    remaining rounds under the clock (decode only — prefills excluded).
    """
    eng = PagedEngine(cfg, params, page_size=page_size, num_pages=256,
                      fused=fused)
    prompts = [rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(n_reqs)]
    for i, p in enumerate(prompts):                       # warmup batch
        eng.submit(Request(i, p, max_new_tokens=new_tokens, temperature=0.0))
    eng.run()
    for i, p in enumerate(prompts):                       # timed batch
        eng.submit(Request(n_reqs + i, p, max_new_tokens=new_tokens,
                           temperature=0.0))
    while eng.queue:
        eng._prefill(eng.queue.pop(0))
    probe_rounds = 2
    base_launch = eng.cache.queue.stats["launches"]
    launches_by_kind = []        # per-round API-level dispatch accounting
    for _ in range(probe_rounds):
        before = dict(eng.cache.queue.launches_by_kind)
        eng._decode_round()
        after = eng.cache.queue.launches_by_kind
        launches_by_kind.append(
            {k: after[k] - before.get(k, 0) for k in after
             if after[k] - before.get(k, 0)})
    dispatches = (eng.cache.queue.stats["launches"] - base_launch) / probe_rounds
    base_tok = eng.stats["tokens_out"]
    t0 = time.perf_counter()
    eng.run()                                             # decode to done
    dt = time.perf_counter() - t0
    decoded = eng.stats["tokens_out"] - base_tok
    return {
        "tok_s": decoded / dt if dt > 0 else float("inf"),
        "decoded_tokens": decoded,
        "dispatches_per_round": dispatches,
        "launches_by_kind_per_round": launches_by_kind,
        "jit_traces": eng.stats["jit_traces"],
    }


def _prefill_throughput(cfg, params, rng, *, fused_prefill: bool,
                        n_reqs: int, lengths, page_size: int):
    """Prefill tokens/s + time-to-first-token for one prefill mode.

    Warmup batch first (the fused path pays one jit trace per distinct
    length bucket), then a timed batch on the same engine: the clock
    covers exactly the prefill round — when it returns, every request
    in the batch has its first token, so the elapsed time IS the
    batch's time-to-first-token.
    """
    eng = PagedEngine(cfg, params, page_size=page_size, num_pages=256,
                      fused_prefill=fused_prefill)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lengths for _ in range(n_reqs)]
    for i, p in enumerate(prompts):                       # warmup batch
        eng.submit(Request(i, p, max_new_tokens=1, temperature=0.0))
    eng.run()
    for i, p in enumerate(prompts):                       # timed batch
        eng.submit(Request(len(prompts) + i, p, max_new_tokens=1,
                           temperature=0.0))
    before = dict(eng.cache.queue.launches_by_kind)
    t0 = time.perf_counter()
    eng._prefill_round()
    ttft = time.perf_counter() - t0
    after = eng.cache.queue.launches_by_kind
    launches = {k: after[k] - before.get(k, 0) for k in after
                if after[k] - before.get(k, 0)}
    toks = sum(len(p) for p in prompts)
    eng.run()                                             # drain
    return {
        "tok_s": toks / ttft if ttft > 0 else float("inf"),
        "ttft_ms": ttft * 1e3,
        "prefill_tokens": toks,
        "launches_by_kind": launches,
        "prefill_jit_traces": eng.stats["prefill_jit_traces"],
    }


def _block_decode_sweep(cfg, params, rng, *, ks, n_reqs, prompt_len,
                        new_tokens, page_size):
    """Table-5 scenario: pure-decode throughput and dispatch cost vs the
    decode block size K.  One engine per K; warmup batch pays the jit
    traces (including the while_loop block step), then a timed batch is
    prefilled outside the clock and decoded to completion under it.
    Dispatches are measured as a queue-level snapshot/delta over the
    timed decode window, so the dispatches-per-token figure counts every
    launch kind — not just the block steps."""
    out = {}
    for k in ks:
        eng = PagedEngine(cfg, params, page_size=page_size, num_pages=256,
                          fused=True, decode_block_rounds=k)
        prompts = [rng.integers(0, cfg.vocab_size, prompt_len)
                   .astype(np.int32) for _ in range(n_reqs)]
        # warmup batch admitted exactly like the timed batch (prefill
        # drained before any decode) so the K-blocks hit the same
        # block-table-width buckets — otherwise the timed window pays a
        # bucket-boundary retrace the warmup never saw
        for rep in range(2):
            for i, p in enumerate(prompts):
                eng.submit(Request(rep * n_reqs + i,
                                   p, max_new_tokens=new_tokens,
                                   temperature=0.0))
            while eng.queue:             # prefill outside the clock
                eng._prefill(eng.queue.pop(0))
            if rep == 0:                                  # warmup batch
                eng.run()
        before = eng.cache.queue.snapshot()
        base_tok = eng.stats["tokens_out"]
        t0 = time.perf_counter()
        eng.run()                                         # decode to done
        dt = time.perf_counter() - t0
        decoded = eng.stats["tokens_out"] - base_tok
        launches = eng.cache.queue.delta(before)
        total = sum(launches.values())
        out[f"K{k}"] = {
            "tok_s": round(decoded / dt if dt > 0 else float("inf"), 2),
            "decoded_tokens": decoded,
            "dispatches_per_token": round(total / max(decoded, 1), 4),
            "launches_by_kind": launches,
            "multi_round_blocks": eng.stats["multi_round_blocks"],
            "block_jit_traces": eng.stats["block_jit_traces"],
        }
    return out


def _mixed_long_prompt(cfg, params, rng, *, chunk, n_decode, decode_new,
                       long_len, page_size):
    """Table-4 scenario: ``n_decode`` short requests decode in flight
    when a ``long_len``-token prompt arrives.  ``chunk=None`` runs the
    monolithic scheduler (the whole prompt prefills in one round);
    otherwise the chunked scheduler streams it ``chunk`` tokens per
    round, decode interleaved.

    Runs the scenario four times on one engine: rep 0 is warmup (pays
    the jit traces), reps 1-3 are measured round by round — the long
    prompt's TTFT (submit -> first token) and the decodes' per-round
    inter-token gaps while any request is still running.  Per rep the
    p99 over those gaps is the starvation number chunking bounds; the
    reported figure is the BEST rep (the systematic prefill-round stall
    shows in every rep, host-load noise spikes do not).
    """
    eng = PagedEngine(cfg, params, page_size=page_size, num_pages=256,
                      max_prefill_chunk=chunk)
    shorts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
              for _ in range(n_decode)]
    long_prompt = rng.integers(0, cfg.vocab_size, long_len).astype(np.int32)
    reps = 4
    ttfts: list = []
    p99s: list = []
    means: list = []
    n_gaps = chunks_per_rep = 0
    for rep in range(reps):              # rep 0 = warmup (pays traces)
        base = rep * (n_decode + 1)
        base_chunks = eng.stats["prefill_chunks"]
        for i, p in enumerate(shorts):
            eng.submit(Request(base + i, p, max_new_tokens=decode_new,
                               temperature=0.0))
        eng.run(max_rounds=2)            # prefill shorts, start decoding
        lid = base + n_decode
        eng.submit(Request(lid, long_prompt, max_new_tokens=1,
                           temperature=0.0))
        t_submit = prev = time.perf_counter()
        counted = {base + i: len(eng.active[base + i].out_tokens)
                   for i in range(n_decode) if base + i in eng.active}
        ttft_ms = None
        gaps: list = []
        while eng.queue or eng.active or eng._chunk_q:
            done = eng.run(max_rounds=1)
            now = time.perf_counter()
            emitted = False
            for rid in counted:
                n = (len(eng.active[rid].out_tokens) if rid in eng.active
                     else len(done.get(rid, [])) or counted[rid])
                emitted |= n > counted[rid]
                counted[rid] = max(counted[rid], n)
            if emitted:
                gaps.append((now - prev) * 1e3)
            if ttft_ms is None and (lid in done or lid in eng.active):
                ttft_ms = (now - t_submit) * 1e3
            prev = now
        if rep:                          # warmup rep is discarded
            ttfts.append(ttft_ms)
            p99s.append(float(np.percentile(gaps, 99)))
            means.append(float(np.mean(gaps)))
            n_gaps = len(gaps)
            chunks_per_rep = eng.stats["prefill_chunks"] - base_chunks
    # decode_stall_rounds deliberately not reported: the engine counter
    # needs a chunk budget to define "over budget", which the monolithic
    # arm (chunk=None) doesn't have — the eager-oracle contrast is
    # regression-tested in tests/test_prefill.py instead, and the
    # starvation story here is told by the p99 gap
    return {
        "ttft_long_ms": round(min(ttfts), 3),
        "decode_itl_p99_ms": round(min(p99s), 3),
        "decode_itl_mean_ms": round(min(means), 3),
        "itl_samples_per_rep": n_gaps,
        "measured_reps": reps - 1,
        "prefill_chunks_per_rep": chunks_per_rep,
    }


def _open_loop_table(cfg, params, *, smoke: bool) -> dict:
    """Table-7 sweep: one open-loop Poisson trace per arrival rate.

    Each rate gets a fresh chunked engine with the radix prefix cache
    and trace recording on, warmed outside the measured trace (one
    throwaway request pays the jit compiles).  The trace itself is the
    multi-tenant workload from :func:`shared_prefix_prompts` — same
    system prompt, per-request tails — driven by
    :func:`poisson_open_loop` under the server's TTFT-SLO admission.
    Afterwards the engine's recorded arena schedule replays on the
    DDR3 twin, pricing every prefix hit as batched RowClone vs the CPU
    re-prefill it avoided."""
    import asyncio

    from repro.launch.serve_async import (poisson_open_loop,
                                          shared_prefix_prompts)
    from repro.serving.server import AsyncServer
    from repro.serving.trace import replay_on_device

    rates = (4.0, 16.0, 64.0)
    n_reqs = 8 if smoke else 24
    prefix_len, tail_len = (16, 4) if smoke else (32, 8)
    max_new = 4 if smoke else 12
    chunk = 16 if smoke else 32
    ttft_slo_ms = 4000.0 if smoke else 2000.0
    deadline_ms = 8000.0 if smoke else 5000.0

    async def run_rate(rate: float) -> dict:
        eng = PagedEngine(cfg, params, page_size=4, num_pages=256,
                          max_prefill_chunk=chunk, prefix_cache=True,
                          record_trace=True)
        eng.submit(Request(10**6,
                           np.arange(prefix_len + tail_len,
                                     dtype=np.int32) % cfg.vocab_size,
                           max_new_tokens=2, temperature=0.0))
        eng.run()                             # warmup: pays the compiles
        prompts = shared_prefix_prompts(n_reqs, cfg.vocab_size,
                                        prefix_len=prefix_len,
                                        tail_len=tail_len)
        srv = AsyncServer(eng, ttft_slo_ms=ttft_slo_ms)
        async with srv:
            res = await poisson_open_loop(srv, prompts, rate,
                                          max_new_tokens=max_new,
                                          deadline_ms=deadline_ms)
        res.pop("streams")
        admitted = srv.stats["admitted"]
        prompt_toks = max(admitted, 1) * (prefix_len + tail_len)
        res["prefix_hit_rate"] = round(
            eng.stats["prefix_hit_tokens"] / prompt_toks, 4)
        res["prefix"] = {k: eng.stats[k] for k in
                         ("prefix_hits", "prefix_hit_tokens",
                          "prefix_evictions")}
        rep = replay_on_device(eng.cache.trace)
        res["replay_speedup"] = rep["speedup"]
        res["prefix_rowclone_ns"] = {
            "cpu_memcpy": rep["cpu_ns"]["prefix_hit_memcpy"],
            "pim_rowclone": rep["pim_ns"]["prefix_hit_rowclone"],
        }
        for k in ("goodput_rps", "goodput_tok_s", "wall_s"):
            res[k] = round(res[k], 3)
        return res

    return {"config": {"requests": n_reqs, "prefix_len": prefix_len,
                       "tail_len": tail_len, "max_new": max_new,
                       "chunk": chunk, "ttft_slo_ms": ttft_slo_ms,
                       "deadline_ms": deadline_ms},
            "rates": {f"rate{r:g}": asyncio.run(run_rate(r))
                      for r in rates}}


def _ambit_table(cfg, params, *, smoke: bool) -> dict:
    """Table-8 scenario: the multi-tenant shared-prefix workload with
    the Ambit zero-compare paths ON (``PagedKVCache.enable_zero_scan``).

    Every sequence free zero-scans its dying pages (already-zero block
    tails skip their init launch), and the prefix-cache teardown audits
    that every freed page really zeroed — the init-on-free security
    invariant verified in-arena.  The recorded trace then replays on the
    cycle-accurate DDR3 twin: RowClone copies/inits price as violated-
    timing AAP sequences, zero scans as Ambit TRA OR-reduces, and the
    timed face now charges tRAS-corrected precharges plus periodic
    refresh — the end-to-end PiM-vs-CPU totals for a real serving
    schedule."""
    from repro.launch.serve_async import shared_prefix_prompts
    from repro.serving.trace import replay_on_device

    n_reqs = 6 if smoke else 16
    prefix_len, tail_len = (16, 4) if smoke else (32, 8)
    max_new = 4 if smoke else 12
    eng = PagedEngine(cfg, params, page_size=4, num_pages=256,
                      max_prefill_chunk=(16 if smoke else 32),
                      prefix_cache=True, record_trace=True)
    eng.cache.enable_zero_scan()
    # warmup outside the recorded workload would pollute the trace; the
    # compile cost lands in wall time only, and this table reports the
    # replayed device-time account, not host throughput
    prompts = shared_prefix_prompts(n_reqs, cfg.vocab_size,
                                    prefix_len=prefix_len,
                                    tail_len=tail_len)
    for i, p in enumerate(prompts):
        eng.submit(Request(i, p, max_new_tokens=max_new, temperature=0.0))
    eng.run()
    evicted = eng.cache.clear_prefix()      # teardown + zero-leak audit
    rep = replay_on_device(eng.cache.trace)
    return {
        "config": {"requests": n_reqs, "prefix_len": prefix_len,
                   "tail_len": tail_len, "max_new": max_new},
        "zero_scan": {k: eng.cache.stats[k] for k in
                      ("init_skips_zero", "zero_audit_pages",
                       "zero_audit_failures")},
        "scan_launches": eng.cache.queue.launches_by_kind.get(
            "page_zero_scan", 0),
        "prefix_nodes_evicted": evicted,
        "trace_counts": rep["counts"],
        "device_stats": rep["device_stats"],
        "pim_ns": rep["pim_ns"],
        "cpu_ns": rep["cpu_ns"],
        "speedup": rep["speedup"],
    }


def _hybrid_long_prompt(rng, *, smoke: bool) -> dict:
    """Table-9 scenario: a jamba-style hybrid stack (mamba + attention +
    MoE sublayers, one paged state arena next to the KV pair) serves the
    paper-scale long-prompt workload — ``long_len`` clipped from the
    100k-token scenario to what the CPU host's naive-attention oracle
    can sweep — chunked through the mixed scheduler while short requests
    decode in flight.

    Three numbers: serving tokens/s over the long prompt's lifetime, a
    two-round pure-decode dispatch probe (the hybrid round must stay ONE
    ``fused_decode``), and the recorded arena schedule replayed on the
    DDR3 twin — a mid-flight fork/free probe puts copy-on-fork and
    init-on-free state rows on the trace so the replay prices them as
    RowClone traffic against the CPU row memcpy/calloc baseline."""
    from repro.serving.trace import replay_on_device

    cfg = reduced(ARCHS["jamba-1.5-large-398b"], num_layers=4,
                  attn_every=4)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(2))
    chunk = 32 if smoke else 256          # multiples of ssm.chunk_size
    long_len = 64 if smoke else 4096
    n_decode = 2 if smoke else 3
    decode_new = 8 if smoke else 24
    num_pages = 64 if smoke else 768
    eng = PagedEngine(cfg, params, page_size=8, num_pages=num_pages,
                      max_prefill_chunk=chunk, record_trace=True)
    # warmup request pays the fused decode/prefill/chunk/mixed traces
    eng.submit(Request(10**6, rng.integers(0, cfg.vocab_size, 16)
                       .astype(np.int32), max_new_tokens=4,
                       temperature=0.0))
    eng.run()
    for i in range(n_decode):             # short requests mid-decode
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, 16)
                           .astype(np.int32), max_new_tokens=decode_new,
                           temperature=0.0))
    eng.run(max_rounds=2)
    # dispatch probe: two pure-decode hybrid rounds
    before = eng.cache.queue.snapshot()
    eng.run(max_rounds=2)
    probe = eng.cache.queue.delta(before)
    # beam-fork probe: copy-on-fork + init-on-free state rows land on
    # the trace (the replay prices them as RowClone vs CPU row memcpy)
    live = sorted(eng.active)[0]
    eng.cache.fork(live, 10**6 + 1)
    eng.cache.free(10**6 + 1)
    # the long hybrid prompt arrives; timed to completion
    lid = 10**6 + 2
    eng.submit(Request(lid, rng.integers(0, cfg.vocab_size, long_len)
                       .astype(np.int32), max_new_tokens=decode_new,
                       temperature=0.0))
    base_tok = eng.stats["tokens_out"]
    t0 = time.perf_counter()
    eng.run()
    dt = time.perf_counter() - t0
    toks = eng.stats["tokens_out"] - base_tok
    rep = replay_on_device(eng.cache.trace)
    return {
        "config": {"long_len": long_len, "chunk": chunk,
                   "n_decode": n_decode, "decode_new": decode_new},
        "tok_s": round((toks + long_len) / dt if dt > 0
                       else float("inf"), 2),
        "decode_tokens": toks,
        "prefill_chunks": eng.stats["prefill_chunks"],
        "mixed_dispatches": eng.stats["mixed_dispatches"],
        "dispatches_per_round": sum(probe.values()) / 2,
        "probe_launches_by_kind": probe,
        "state_stats": {"state_forks": eng.stats["state_forks"],
                        "prefix_declined_ssm":
                            eng.stats["prefix_declined_ssm"]},
        "state_replay_ns": {
            k: rep["pim_ns"][k] for k in
            ("state_rowclone_copy", "state_rowclone_init",
             "state_write_cpu")},
        "state_replay_cpu_ns": {
            k: rep["cpu_ns"][k] for k in
            ("state_memcpy", "state_calloc", "state_write_cpu")},
        "replay_speedup": {k: rep["speedup"][k] for k in
                           ("state_copy", "state_init", "end_to_end")},
    }


def _mesh_row_local(world: int, compressed: bool, smoke: bool) -> dict:
    """Measure one (mesh, collective) cell IN THIS PROCESS — requires
    ``jax.device_count() >= world``.  Same shape as table 2: warmup
    batch pays the traces, then a timed batch gives batch TTFT (the
    prefill round), a two-round dispatch probe, and decode tokens/s.
    The per-shard attribution comes straight from
    ``PimOpQueue.snapshot(by_owner=True)`` over the timed window."""
    cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    mesh = make_local_mesh(model=world)
    n_reqs, new_tokens = (2, 8) if smoke else (4, 16)
    eng = PagedEngine(cfg, params, page_size=4, num_pages=256, mesh=mesh,
                      compressed_collectives=compressed)
    prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(n_reqs)]
    # two warmup batches: the first pays the jit traces, the second pays
    # the one-time XLA relowering for the post-round arena shardings
    # (sharded arrays returned by the fused step key the executable
    # cache differently from the freshly device_put arenas — no Python
    # retrace, but one extra compile on the first post-warmup round)
    for rep in range(2):
        for i, p in enumerate(prompts):
            eng.submit(Request(rep * n_reqs + i, p,
                               max_new_tokens=new_tokens, temperature=0.0))
        eng._prefill_round()
        eng.run()
    for i, p in enumerate(prompts):                       # timed batch
        eng.submit(Request(2 * n_reqs + i, p, max_new_tokens=new_tokens,
                           temperature=0.0))
    owner_base = eng.cache.queue.snapshot(by_owner=True)
    t0 = time.perf_counter()
    eng._prefill_round()
    ttft = time.perf_counter() - t0
    probe_rounds = 2
    base_launch = eng.cache.queue.stats["launches"]
    for _ in range(probe_rounds):
        eng._decode_round()
    dispatches = (eng.cache.queue.stats["launches"]
                  - base_launch) / probe_rounds
    base_tok = eng.stats["tokens_out"]
    t0 = time.perf_counter()
    eng.run()                                             # decode to done
    dt = time.perf_counter() - t0
    decoded = eng.stats["tokens_out"] - base_tok
    return {
        "mesh": world,
        "collective": "psum_compressed" if compressed else "psum",
        "decode_tok_s": round(decoded / dt if dt > 0 else float("inf"), 2),
        "ttft_ms": round(ttft * 1e3, 3),
        "dispatches_per_round": dispatches,
        "launches_by_owner": eng.cache.queue.delta(owner_base,
                                                   by_owner=True),
    }


def _mesh_table(smoke: bool) -> dict:
    """Table 6 sweep, every cell in this process on ``jax.devices()``:
    one process holds all of a host's chips, and a child process could
    not reach them.  A CPU host has N devices only under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before
    the run; a mesh wider than the process's devices is recorded as
    skipped, and a cell that fails fails the run."""
    rows: dict = {}
    n_dev = jax.device_count()
    for world in (1, 2, 4):
        for compressed in (False, True):
            key = f"mesh{world}_" + ("psum_compressed" if compressed
                                     else "psum")
            if world > n_dev:
                rows[key] = {"skipped": f"needs {world} devices, this "
                                        f"process has {n_dev}"}
                continue
            rows[key] = _mesh_row_local(world, compressed, smoke)
    return rows


def main(out=sys.stdout, smoke: bool = False):
    print("name,us_per_call,derived", file=out)
    cfg = reduced(ARCHS["granite-3-8b"], num_layers=2)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    N, NEW, PS = (2, 6, 8) if smoke else (6, 4, 8)

    # ---- table 1: shared-prefix workload WITH pim page ops ------------- #
    t0 = time.perf_counter()
    eng = PagedEngine(cfg, params, page_size=PS, num_pages=128)
    for i in range(N):
        eng.submit(Request(i, prompt, max_new_tokens=NEW, temperature=0.0,
                           share_with=0 if i else None,
                           shared_len=(len(prompt) // PS) * PS if i else 0))
    res = eng.run()
    us_pim = (time.perf_counter() - t0) * 1e6
    kv_bytes_per_tok = (cfg.num_layers * 2 * cfg.num_kv_heads
                        * cfg.resolved_head_dim * 2)
    shared_toks = (len(prompt) // PS) * PS * (N - 1)
    saved = shared_toks * kv_bytes_per_tok
    print(f"serve_pim_prefix_sharing,{us_pim:.0f},"
          f"prefill_kv_bytes_saved={saved}", file=out)
    print(f"serve_pim_stats,0,prefix_hits={eng.cache.stats['prefix_hits']}"
          f";cow={eng.cache.stats['cow_copies']}"
          f";zeroed={eng.cache.stats['pages_zeroed']}", file=out)

    # naive: every request prefills its full prompt (no sharing)
    t0 = time.perf_counter()
    eng2 = PagedEngine(cfg, params, page_size=PS, num_pages=128)
    for i in range(N):
        eng2.submit(Request(i, prompt, max_new_tokens=NEW, temperature=0.0))
    res2 = eng2.run()
    us_naive = (time.perf_counter() - t0) * 1e6
    print(f"serve_naive_no_sharing,{us_naive:.0f},"
          f"speedup={us_naive/us_pim:.2f}x", file=out)
    assert res[0] == res2[0]

    # ---- table 2: fused single-dispatch decode round vs eager loop ----- #
    dec = dict(n_reqs=(2 if smoke else 4), prompt_len=16,
               new_tokens=(8 if smoke else 16), page_size=4)
    fstats = _decode_throughput(cfg, params, rng, fused=True, **dec)
    estats = _decode_throughput(cfg, params, rng, fused=False, **dec)
    speedup = fstats["tok_s"] / estats["tok_s"]
    print(f"decode_fused,{1e6/max(fstats['tok_s'],1e-9):.0f},"
          f"tok_s={fstats['tok_s']:.1f}"
          f";dispatches_per_round={fstats['dispatches_per_round']:.1f}"
          f";jit_traces={fstats['jit_traces']}", file=out)
    print(f"decode_eager,{1e6/max(estats['tok_s'],1e-9):.0f},"
          f"tok_s={estats['tok_s']:.1f}"
          f";dispatches_per_round={estats['dispatches_per_round']:.1f}",
          file=out)
    print(f"decode_fusion_speedup,0,{speedup:.2f}x", file=out)

    # ---- table 3: fused bucketed prefill vs eager per-request path ----- #
    pre = dict(n_reqs=(2 if smoke else 4), lengths=(16, 32), page_size=4)
    pstats = _prefill_throughput(cfg, params, rng, fused_prefill=True, **pre)
    qstats = _prefill_throughput(cfg, params, rng, fused_prefill=False, **pre)
    pspeed = pstats["tok_s"] / qstats["tok_s"]
    print(f"prefill_fused,{1e6/max(pstats['tok_s'],1e-9):.0f},"
          f"tok_s={pstats['tok_s']:.1f};ttft_ms={pstats['ttft_ms']:.1f}"
          f";jit_traces={pstats['prefill_jit_traces']}", file=out)
    print(f"prefill_eager,{1e6/max(qstats['tok_s'],1e-9):.0f},"
          f"tok_s={qstats['tok_s']:.1f};ttft_ms={qstats['ttft_ms']:.1f}",
          file=out)
    print(f"prefill_fusion_speedup,0,{pspeed:.2f}x", file=out)

    # ---- table 4: chunked prefill under long-prompt mixed traffic ------ #
    # full config: the long prompt must be long enough that one chunk
    # round (O(long*chunk) attention) clearly beats the monolithic
    # prefill round (O(long^2)) — below ~1k tokens the per-chunk gather
    # overhead and round-time noise can invert the p99 comparison on
    # CPU; decode_new must exceed long_len/chunk so the short requests
    # are still decoding while every chunk streams through
    mix = dict(n_decode=(2 if smoke else 3),
               decode_new=(12 if smoke else 40),
               long_len=(64 if smoke else 1024), page_size=8)
    chunk_size = 16 if smoke else 32
    cstats = _mixed_long_prompt(cfg, params, rng, chunk=chunk_size, **mix)
    mstats = _mixed_long_prompt(cfg, params, rng, chunk=None, **mix)
    itl_ratio = mstats["decode_itl_p99_ms"] / max(cstats["decode_itl_p99_ms"],
                                                  1e-9)
    print(f"mixed_chunked,0,ttft_long_ms={cstats['ttft_long_ms']:.1f}"
          f";itl_p99_ms={cstats['decode_itl_p99_ms']:.2f}"
          f";chunks={cstats['prefill_chunks_per_rep']}", file=out)
    print(f"mixed_monolithic,0,ttft_long_ms={mstats['ttft_long_ms']:.1f}"
          f";itl_p99_ms={mstats['decode_itl_p99_ms']:.2f}", file=out)
    print(f"mixed_itl_p99_improvement,0,{itl_ratio:.2f}x", file=out)

    # ---- table 5: multi-round decode blocking, dispatches/token vs K --- #
    blk = dict(ks=(1, 4, 8), n_reqs=(2 if smoke else 4), prompt_len=8,
               new_tokens=(16 if smoke else 32), page_size=4)
    bstats = _block_decode_sweep(cfg, params, rng, **blk)
    for key, s in bstats.items():
        print(f"decode_block_{key},{1e6/max(s['tok_s'],1e-9):.0f},"
              f"tok_s={s['tok_s']:.1f}"
              f";dispatches_per_token={s['dispatches_per_token']:.3f}"
              f";multi_round_blocks={s['multi_round_blocks']}", file=out)
    blk_ratio = (bstats["K1"]["dispatches_per_token"]
                 / max(bstats["K8"]["dispatches_per_token"], 1e-9))
    print(f"decode_block_dispatch_reduction,0,{blk_ratio:.2f}x", file=out)

    # ---- table 6: tensor-parallel mesh x logit-collective sweep -------- #
    mrows = _mesh_table(smoke)
    for key, row in mrows.items():
        if "decode_tok_s" in row:
            print(f"sharded_{key},{1e6/max(row['decode_tok_s'],1e-9):.0f},"
                  f"tok_s={row['decode_tok_s']:.1f}"
                  f";ttft_ms={row['ttft_ms']:.1f}"
                  f";dispatches_per_round={row['dispatches_per_round']:.1f}",
                  file=out)
        else:
            print(f"sharded_{key},0,skipped={row['skipped']}", file=out)

    # ---- table 7: open-loop Poisson sweep, goodput under SLO ----------- #
    orows = _open_loop_table(cfg, params, smoke=smoke)
    for key, row in orows["rates"].items():
        print(f"open_loop_{key},0,goodput_rps={row['goodput_rps']:.2f}"
              f";rejected={row['rejected']}/{row['requests']}"
              f";ttft_p99_ms={row['ttft_p99_ms'] or float('nan'):.1f}"
              f";prefix_hit_rate={row['prefix_hit_rate']:.3f}"
              f";prefix_rowclone_speedup="
              f"{row['replay_speedup']['prefix'] or float('nan'):.1f}x",
              file=out)

    # ---- table 8: Ambit zero-compare + timed-face replay totals -------- #
    arows = _ambit_table(cfg, params, smoke=smoke)
    z = arows["zero_scan"]
    print(f"ambit_zero_scan,0,"
          f"init_skips_zero={z['init_skips_zero']}"
          f";audit_pages={z['zero_audit_pages']}"
          f";audit_failures={z['zero_audit_failures']}"
          f";scan_launches={arows['scan_launches']}", file=out)
    e2e = arows["speedup"]["end_to_end"] or float("nan")
    zsc = arows["speedup"]["zero_scan"] or float("nan")
    print(f"ambit_replay_totals,0,"
          f"pim_total_ns={arows['pim_ns']['total']:.0f}"
          f";cpu_total_ns={arows['cpu_ns']['total']:.0f}"
          f";end_to_end={e2e:.2f}x;zero_scan={zsc:.2f}x"
          f";refreshes={arows['device_stats']['refreshes']}", file=out)

    # ---- table 9: jamba-style hybrid long-prompt serving --------------- #
    hrows = _hybrid_long_prompt(rng, smoke=smoke)
    print(f"hybrid_long_prompt,0,tok_s={hrows['tok_s']:.1f}"
          f";long_len={hrows['config']['long_len']}"
          f";dispatches_per_round={hrows['dispatches_per_round']:.1f}"
          f";prefill_chunks={hrows['prefill_chunks']}", file=out)
    hsp = hrows["replay_speedup"]
    print(f"hybrid_state_replay,0,"
          f"state_copy={(hsp['state_copy'] or float('nan')):.1f}x"
          f";state_init={(hsp['state_init'] or float('nan')):.1f}x"
          f";state_write_cpu_ns="
          f"{hrows['state_replay_ns']['state_write_cpu']:.0f}", file=out)

    bench = {
        "config": {"arch": "granite-3-8b (reduced)", "smoke": smoke, **dec,
                   "prefill": pre},
        "decode_tok_s_fused": round(fstats["tok_s"], 2),
        "decode_tok_s_eager": round(estats["tok_s"], 2),
        "decode_fusion_speedup": round(speedup, 2),
        "dispatches_per_round_fused": fstats["dispatches_per_round"],
        "dispatches_per_round_eager": estats["dispatches_per_round"],
        # opcode-level dispatch accounting per probed round (pimolib v2:
        # PimOpQueue.launches_by_kind is the one source of truth)
        "launches_by_kind_per_round_fused": fstats["launches_by_kind_per_round"],
        "launches_by_kind_per_round_eager": estats["launches_by_kind_per_round"],
        "jit_traces_fused": fstats["jit_traces"],
        "decoded_tokens": fstats["decoded_tokens"],
        # fused bucketed prefill vs the eager per-request oracle
        "prefill_tok_s_fused": round(pstats["tok_s"], 2),
        "prefill_tok_s_eager": round(qstats["tok_s"], 2),
        "prefill_fusion_speedup": round(pspeed, 2),
        "prefill_ttft_ms_fused": round(pstats["ttft_ms"], 3),
        "prefill_ttft_ms_eager": round(qstats["ttft_ms"], 3),
        "prefill_launches_by_kind_fused": pstats["launches_by_kind"],
        "prefill_launches_by_kind_eager": qstats["launches_by_kind"],
        "prefill_jit_traces_fused": pstats["prefill_jit_traces"],
        "prefill_tokens": pstats["prefill_tokens"],
        # table 4: long-prompt mixed traffic, chunked vs monolithic
        "mixed_config": {**mix, "max_prefill_chunk": chunk_size},
        "mixed_chunked": cstats,
        "mixed_monolithic": mstats,
        "mixed_itl_p99_improvement": round(itl_ratio, 2),
        # table 5: multi-round decode blocking (decode_block_rounds=K)
        "block_decode_config": {k: v for k, v in blk.items() if k != "ks"},
        "block_decode_sweep": bstats,
        "block_decode_dispatch_reduction": round(blk_ratio, 2),
        # table 6: tensor-parallel mesh x collective sweep (mesh>1 cells
        # record a skip note on hosts below 4 cores)
        "mesh_sweep": mrows,
        # table 7: open-loop Poisson sweep through the async server —
        # goodput under SLO, prefix-cache hit rate, replayed RowClone
        # savings per arrival rate
        "open_loop_sweep": orows,
        # table 8: Ambit zero-compare consumer + cycle-accurate replay
        # (tRAS-corrected + refresh-inclusive PiM totals vs all-CPU)
        "ambit_zero_scan": arows,
        # table 9: jamba-style hybrid long-prompt serving — one dispatch
        # per hybrid decode round, state-arena RowClone replay savings
        "hybrid_serving": hrows,
    }
    path = BENCH_JSON_SMOKE if smoke else BENCH_JSON
    with open(path, "w") as f:
        json.dump(bench, f, indent=2)
    print(f"# wrote {path}", file=out)


if __name__ == "__main__":
    enable_compile_cache()
    main(smoke="--smoke" in sys.argv)
