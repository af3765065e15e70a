"""Continuous-batching serving engine over the paged PiM KV cache.

Request lifecycle: queue -> prefill (model prefill pass, KV written into
arena pages) -> decode rounds (paged attention over block tables, one
token per active sequence per round, new arrivals join between rounds)
-> finish (pages freed with pim_init, stats recorded).

The engine runs the *paged* attention path: per-layer KV lives only in
the arena; the model's dense-cache path is never materialized.  Forking
(`n>1` samples sharing a prompt) uses the cache's RowClone CoW.
Sampling consumes the D-RaNGe TPU generator (`pim_rand`).

A decode round is ONE compiled dispatch (the fused decode step):

* the layer loop is a ``jax.lax.scan`` over the stacked ``group0``
  params and the layer index, so the traced program is O(1) in depth;
  the arenas are loop-invariant and the paged-attention kernel reads
  each layer's pages in place (on a TPU always, elsewhere with
  ``use_pallas``; the jnp reference otherwise);
* the current token's K/V merge into attention happens *inside* the
  paged-attention kernel (``k_self``/``v_self``) — no post-kernel pass
  re-reads the arena history;
* the round's KV scatter and the token selection (greedy argmax or
  D-RaNGe inverse-CDF sample, per request) run in the same jit, with
  both arenas donated on backends that support donation, so the round
  issues no separate mutation launch and exactly one device->host
  transfer (the chosen tokens);
* block-table widths and the active batch are bucketed to powers of two
  (padding rows duplicate sequence 0, whose duplicate scatter writes
  identical values to identical slots), so growing/forking workloads
  retrace only at bucket boundaries — ``stats["jit_traces"]`` counts
  retraces, ``PimOpQueue`` counts dispatches.

Pre-round CoW copies still route through the cache's batched PiM op
scheduler: one coalesced copy flush (only when some sequence forks)
lands before the fused step reads the arena.  ``fused=False`` keeps the
pre-fusion eager path (a Python loop over layers, one launch per layer)
as the benchmark baseline.

A prefill batch is ONE compiled dispatch too (the fused bucketed
prefill, symmetric with the decode round):

* queued prompts are bucketed by length to powers of two (padding
  positions carry attention-masked tokens) and stacked into one batch
  per bucket, the batch itself bucketed to a power of two (padding rows
  duplicate request 0);
* the forward is a ``jax.lax.scan`` over the stacked layer params with
  a length-masked flash-attention prefill
  (``repro.kernels.flash_attention``, ``lengths`` masking), so the
  traced program is O(1) in depth and a batch retraces only per
  distinct (length-bucket, batch-bucket) pair —
  ``stats["prefill_jit_traces"]`` counts retraces;
* every prompt's new KV pages scatter straight into the donated arenas
  *inside* the jit (``rc_ops.kv_scatter_inline`` against the cache's
  host-side ``prefill_scatter_plan``), recorded through ``PimOpQueue``
  accounting as the ``fused_prefill`` kind — no host-side
  ``write_prompt_kv`` round-trip;
* first-token selection runs in the same jit with one host transfer
  per batch.

When forking/active sequences coexist with queued prompts, the
pre-round CoW copy flush is dispatched *before* the prefill host work
(``PimOpQueue.flush_overlapped``), so the coalesced copies execute on
device behind prefill batch assembly instead of stalling the decode
round.  ``fused_prefill=False`` keeps the eager per-request path (one
un-jitted dense ``T.forward`` per prompt + host-side KV writes) as the
parity oracle and benchmark baseline.  The oracle contract is exact for
greedy requests (``temperature == 0``); sampled requests draw one TRNG
seed per fused *batch* vs one per eager *request*, so the two modes'
random streams — and therefore sampled tokens — legitimately differ.

Chunked prefill with decode-interleaved scheduling
(``max_prefill_chunk=N``): a monolithic prefill batch makes in-flight
decodes wait behind the whole prompt, so a long arriving prompt
stretches every active request's inter-token latency by its full
forward.  With a chunk budget set, prompts are split into
``max_prefill_chunk``-sized chunks processed across successive engine
rounds — each round runs at most ONE fused chunk batch (pending chunks
fill the round's token budget, FIFO, same chunk-length bucket) *and*
the fused decode round, so decodes emit a token every round regardless
of arriving prompt length.  A chunk's queries attend causally over the
chunk itself **plus**, non-causally, the sequence's already-committed
arena KV (the flash kernel's prefix-KV operands; the prefix rides in as
an in-scan arena gather over the sequence's block table, masked by the
committed length).  Chunk KV scatters in-jit against the cache's
per-chunk ``prefill_scatter_plan(start, stop)`` and is accounted as the
same ``fused_prefill`` kind.  The prefix block table spans the
sequence's FULL page list (valid length = committed tokens), so every
chunk of one prompt shares one table-width bucket and chunk batches
retrace only per distinct (chunk-bucket, batch-bucket, table-width)
triple — never per chunk count.  ``stats["prefill_chunks"]`` counts
chunks dispatched; ``stats["decode_stall_rounds"]`` counts rounds in
which active decodes waited behind an over-budget (un-chunked) prefill
— structurally zero when chunking is on, nonzero for the eager oracle
fed the same long-prompt workload.

Multi-round fusion (the "one dispatch per N rounds" step).  Two layers:

* **Mixed rounds** (``mixed_rounds=True``, chunked + fused): a round
  that runs both a chunk batch and a decode round used to cost two
  jitted dispatches; they already share ``_sublayer``, the donated
  arenas, and bucketed shapes, so the engine traces them as ONE program
  — the chunk half's first tokens wire straight into the decode half's
  inputs (``d_from_chunk``), the chunk KV scatter is traced before the
  decode forward so a prompt finishing this round decodes against its
  own just-written KV, and the whole mixed round is accounted as ONE
  ``fused_mixed`` launch (``stats["mixed_dispatches"]``).
* **K-blocked decode** (``decode_block_rounds=K``): when no admissions
  are pending, the engine runs up to K decode rounds inside a
  ``jax.lax.while_loop`` in ONE dispatch — one host round-trip (and one
  token transfer) per K tokens.  The host reserves every row's K-token
  arena capacity up front (``PagedKVCache.reserve_tokens``) so each
  in-loop round has a host-planned (page, slot) destination; in-loop
  stop detection covers per-request EOS and token budgets, and a row
  that stops writes the value *already in its slot* back to it (a
  masked write-back via ``kv_gather_inline``) so the scatter stays a
  structural no-op for dead rows and the arena is bit-identical to a
  round-at-a-time run.  Blocks are counted in
  ``stats["multi_round_blocks"]``; the per-block launch is the
  ``fused_decode_block`` kind, so dispatches-per-token falls below 1
  after warmup.  ``decode_block_rounds=1`` (default) and the eager path
  are kept as round-at-a-time oracles.

Tensor-parallel sharded serving (``mesh=``): pass a mesh with a
``model`` axis and every fused step runs as a ``shard_map`` program
spanning all N devices — still ONE dispatch per round.  Layer params
shard Megatron-style over their logical axes (``heads`` / ``kv_heads``
/ ``ff`` / ``vocab`` -> ``model``; the spec tree comes from
``models.params.param_specs`` under a ``sharding_env``), the KV arenas
split on the KV-head axis (each device holds its head slice of every
page — page ids, block tables, and the op queue stay mesh-wide), and
block tables / lengths / sampling state are replicated.  Inside the
program: vocab-parallel embedding and logits (masked local lookup /
local partial logits placed at ``axis_index * V_local``, both reduced
with an exact-zeros ``psum`` — bit-identical to host-local math),
row-parallel attention-out and MLP-down ``psum``s (the only float
reordering vs host-local), and the final logit reduce routed through
``distributed.compression.psum_compressed`` when
``compressed_collectives=True`` (int8 wire traffic, logits within
quantization tolerance).  Token selection runs replicated from the full
logits, so every shard picks the same token and the round's single
host transfer is unchanged.  CPU dev boxes get a real multi-device
mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Hybrid layouts (SSM / MoE sublayers).  The engine serves every
single-group decoder in the zoo — dense GQA, pure-SSM (mamba2), hybrid
attention+Mamba+MoE (jamba), and non-MLA MoE — with per-layer-kind
dispatch *inside* the existing ``lax.scan`` (:func:`_run_kinds`), so a
hybrid decode round is still ONE compiled dispatch:

* attention sublayers keep the paged KV arenas exactly as above;
* Mamba sublayers carry per-sequence recurrent state in the cache's
  :class:`~repro.serving.kv_cache.PagedStateArena` — constant-size rows
  (no growth, no prefix sharing, copy-on-fork), gathered at the batch's
  state rows and scattered back in-jit, so the fused steps add zero
  launches (the scan's xs extend with the (conv, ssm) arenas and the
  updated arenas ride out as stacked ys on donated buffers);
* MoE sublayers route in-jit through the exact dense-fallback MoE
  (``models.moe._dense_moe`` — per-token independent, jit-traceable),
  so expert routing adds zero launches and the eager oracle stays
  bit-identical;
* the eager paths pay their state writes through the op queue's
  ``ssm_state_write`` kind instead (the ``SSM_STATE_WRITE`` opcode's
  JAX face): ONE coalesced state-scatter launch per arena per round,
  constant in depth and batch, hazard-tracked against copy-on-fork.

Chunked prefill over SSM layers must split prompts at multiples of
``cfg.ssm.chunk_size`` — the SSD chunk scan regroups bit-identically
only at chunk boundaries — so the engine requires
``max_prefill_chunk % chunk_size == 0`` for state-arena families.
MLA (latent-KV) and multi-group layouts still serve through the dense
path; ``mesh=`` serving stays dense-only.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig
from repro.distributed.compression import psum_compressed
from repro.distributed.sharding import sharding_env
from repro.kernels.drange import ops as dr_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.rowclone import ops as rc_ops
from repro.models import moe as moe_mod
from repro.models import params as P_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as T
from repro.models.layers import (rmsnorm, cast, logits_out, embed, mlp,
                                 apply_rope, rope_sincos)
from .kv_cache import PagedKVCache, _bucket_pow2

# Host spans of the serving round (``engine.round`` and its phases), on
# the profiler's clock beside the device's ops; with no profiler running
# each costs only the annotation's enter/exit.  Host code only: never
# inside a traced function.
_span = jax.profiler.TraceAnnotation


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray                    # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 1.0
    # stop generating after emitting this token (the EOS token itself is
    # kept in out_tokens); None = budget-only stopping.  The K-blocked
    # decode loop detects this on device, between host round-trips.
    eos_token_id: Optional[int] = None
    share_with: Optional[int] = None      # prefix sharing source
    shared_len: int = 0
    # keep_logits: store in ``logits`` the (vocab,) logits that chose
    # out_tokens[0] — the prefill's last prompt position, left on the
    # device — for callers that check the model's output, not only its
    # tokens
    keep_logits: bool = False
    logits: Optional[jax.Array] = None
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False


@dataclass
class _ChunkPrefill:
    """A mid-prefill request on the chunk backlog: ``off`` tokens of its
    prompt (shared prefix included) are committed to the arena.

    ``dep``/``dep_len``: a prefix-sharing request reads its *source*
    sequence's pages — under chunked prefill those commit across rounds,
    so this state may not be scheduled until the source has committed at
    least ``dep_len`` tokens (the monolithic path never sees this hazard
    because all prefill completes before any decode).

    ``write=False``: a prompt fully covered by a shared prefix has no KV
    of its own to commit — it runs as a single 1-token chunk (the last
    prompt position recomputed against the committed prefix) whose
    scatter is suppressed, so even a very long covered sharer costs one
    bounded chunk round, never a whole-prompt forward."""
    req: Request
    off: int
    dep: Optional[int] = None
    dep_len: int = 0
    write: bool = True

    @property
    def remaining(self) -> int:
        return len(self.req.prompt) - self.off


def _keep_logits(req: Request, logits: jax.Array, row: int) -> None:
    """Store row ``row`` of a prefill's logits on a request that asked."""
    if req.keep_logits:
        req.logits = logits[row]


class PagedEngine:
    """Single-host engine for GQA decoder-only models (the paged path)."""

    def __init__(self, cfg: ModelConfig, params, *, page_size: int = 16,
                 num_pages: int = 256, pcfg: Optional[ParallelConfig] = None,
                 seed: int = 0, use_pallas: bool = False, fused: bool = True,
                 fused_prefill: bool = True,
                 max_prefill_chunk: Optional[int] = None,
                 decode_block_rounds: int = 1, mixed_rounds: bool = True,
                 lib=None, record_trace: bool = False,
                 mesh=None, compressed_collectives: bool = False,
                 prefix_cache: bool = False):
        assert cfg.family in ("dense", "vlm", "ssm", "hybrid", "moe"), \
            "paged engine: decoder-only GQA / SSM / hybrid / MoE archs"
        if cfg.mla is not None:
            raise ValueError(
                "paged engine: MLA latent-KV attention is not paged — "
                "deepseek-style archs serve through the dense path")
        if len(T.layer_groups(cfg)) != 1:
            raise ValueError(
                "paged engine: single-group layouts only (leading dense "
                "layers split the scan; set first_dense_layers=0)")
        self.cfg = cfg
        self.pcfg = pcfg or ParallelConfig(attention_impl="naive", remat="none")
        # per-scan-step sublayer kinds — the hybrid dispatch plan every
        # forward (fused scans AND the eager oracle) follows in lockstep
        self._kinds = T.layer_groups(cfg)[0][1]
        self._has_attn = "attn" in self._kinds
        self._has_ssm = "mamba" in self._kinds
        self._has_moe = "moe" in self._kinds
        # tensor-parallel sharded serving: fused steps become shard_map
        # programs over the mesh's `model` axis (see module docstring)
        self.mesh = mesh
        self.compressed_collectives = compressed_collectives
        if compressed_collectives and mesh is None:
            raise ValueError("compressed_collectives requires mesh=")
        self._param_specs = None
        self._arena_spec = None
        if mesh is not None:
            if self._has_ssm or self._has_moe:
                raise ValueError(
                    "paged engine: mesh= serving is dense-only (SSM state "
                    "arenas and in-jit MoE routing are host-local)")
            if "model" not in dict(mesh.shape):
                raise ValueError("engine mesh needs a 'model' axis")
            n = mesh.shape["model"]
            if n > 1:
                bad = {name: dim for name, dim in
                       (("num_heads", cfg.num_heads),
                        ("num_kv_heads", cfg.num_kv_heads),
                        ("d_ff", cfg.d_ff),
                        ("vocab_size", cfg.vocab_size))
                       if dim % n != 0}
                if bad:
                    # resolve_spec would silently replicate a non-divisible
                    # dim, and the steps' unconditional psums would then
                    # over-count that path by N — refuse instead
                    raise ValueError(
                        f"model dims {bad} not divisible by mesh model "
                        f"axis {n}")
            with sharding_env(mesh, fsdp=False):
                self._param_specs = P_mod.param_specs(T.model_defs(cfg))
            shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._param_specs,
                is_leaf=lambda s: isinstance(s, P))
            params = jax.device_put(params, shardings)
            self._arena_spec = P(None, None, None, "model", None)
        self.params = params
        # lib: caller-supplied JAX-face PimLib (pimolib v2) the cache
        # binds its arenas to — shares the op queue / launch accounting;
        # record_trace: keep a PimTrace for model-face replay
        # prefix_cache: radix-tree prefix cache over pages — prompts
        # automatically attach the longest committed full-page prefix of
        # any earlier prompt (create(..., tokens=)), committed prompts
        # index on completion (commit_prefix), cold entries evict LRU
        # under arena pressure
        self.prefix_cache = prefix_cache
        self.cache = PagedKVCache(cfg, num_pages=num_pages,
                                  page_size=page_size, use_pallas=use_pallas,
                                  lib=lib, record_trace=record_trace,
                                  mesh=mesh, prefix_cache=prefix_cache)
        # Pallas kernels (True) or their jnp references; how a kernel
        # runs — compiled on a TPU, interpreted elsewhere — is the
        # backend's call (repro.kernels.common.interpret_mode).  Decode
        # attention takes its kernel on a TPU whatever this says
        # (pa_ops.uses_kernel)
        self.use_pallas = use_pallas
        self.fused = fused
        self.fused_prefill = fused_prefill
        if max_prefill_chunk is not None and max_prefill_chunk < 1:
            raise ValueError("max_prefill_chunk must be >= 1 (or None to "
                             "disable chunked prefill)")
        if (max_prefill_chunk is not None and self._has_ssm
                and max_prefill_chunk % cfg.ssm.chunk_size != 0):
            raise ValueError(
                f"max_prefill_chunk={max_prefill_chunk} must be a multiple "
                f"of cfg.ssm.chunk_size={cfg.ssm.chunk_size}: the SSD chunk "
                "scan only regroups bit-identically when prompts split at "
                "chunk-size boundaries")
        # chunked prefill: prompts longer than this are split into
        # chunk-sized pieces processed across successive rounds, decode
        # interleaved (None = monolithic: a prompt prefills whole)
        self.max_prefill_chunk = max_prefill_chunk
        if decode_block_rounds < 1:
            raise ValueError("decode_block_rounds must be >= 1")
        if decode_block_rounds > 1 and not fused:
            raise ValueError("decode_block_rounds > 1 requires fused=True "
                             "(the eager path is the round-at-a-time oracle)")
        # persistent decode loop: with no admissions pending, run up to K
        # decode rounds per host round-trip in one lax.while_loop dispatch
        # (1 = round-at-a-time, the single-round fused oracle)
        self.decode_block_rounds = decode_block_rounds
        # fuse a round's chunk batch + decode round into one dispatch
        # (only reachable with chunking + both fused paths on)
        self.mixed_rounds = mixed_rounds
        self.queue: List[Request] = []
        self.active: Dict[int, Request] = {}
        # chunk backlog: requests mid-prefill under the chunked scheduler
        self._chunk_q: List[_ChunkPrefill] = []
        self._chunk_by_id: Dict[int, _ChunkPrefill] = {}
        self.rng_seed = jnp.asarray([seed, seed ^ 0x9E3779B9], jnp.uint32)
        self.rng_ctr = 0
        self.stats = {"prefills": 0, "decode_rounds": 0, "tokens_out": 0,
                      "jit_traces": 0, "fused_dispatches": 0,
                      "prefill_jit_traces": 0, "fused_prefill_dispatches": 0,
                      "prefill_chunks": 0, "decode_stall_rounds": 0,
                      "multi_round_blocks": 0, "block_jit_traces": 0,
                      "mixed_dispatches": 0, "mixed_jit_traces": 0,
                      "prefix_hits": 0, "prefix_hit_tokens": 0,
                      "prefix_evictions": 0,
                      "state_pages": 0, "state_forks": 0,
                      "prefix_declined_ssm": 0,
                      # useful over attempted work of the padded launches:
                      # arena tokens the decode rows attend over against
                      # the token slots the launches read (each padded
                      # row's length rounded up to a page on the kernel;
                      # rows x table width x page size on the reference),
                      # and real chunk tokens against rows x chunk bucket
                      "decode_kv_tokens": 0, "decode_kv_slots": 0,
                      "chunk_tokens": 0, "chunk_token_slots": 0}
        self._step = self._build_fused_step() if fused else None
        self._prefill_step = (self._build_fused_prefill_step()
                              if fused_prefill else None)
        self._chunk_step = (self._build_fused_chunk_step()
                            if fused_prefill and max_prefill_chunk is not None
                            else None)
        self._block_step = (self._build_fused_block_step()
                            if fused and decode_block_rounds > 1 else None)
        self._mixed_step = (self._build_fused_mixed_step()
                            if mixed_rounds and self._chunk_step is not None
                            and fused else None)
        # decode tails already reserved this round (the pre-prefill
        # overlap path reserves early; _decode_round must not re-reserve)
        self._reserved_tails: set = set()

    # ----------------------------- API -------------------------------- #

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    @property
    def has_work(self) -> bool:
        """Anything queued, mid-prefill, or decoding?"""
        return bool(self.queue or self._chunk_q or self.active)

    def prefill_backlog_tokens(self) -> int:
        """Prompt tokens admitted but not yet committed to the arena:
        the chunk backlog's remaining work plus everything still in the
        submit queue.  The server's admission control divides this by
        the chunk budget to estimate how many rounds a new prompt waits
        before its first token."""
        return (sum(st.remaining for st in self._chunk_q)
                + sum(len(r.prompt) for r in self.queue))

    def set_prefill_chunk(self, n: int) -> None:
        """Retarget the per-round prefill chunk budget at runtime — the
        server's auto-tuner hook.  Legal only when the engine was built
        chunked (``max_prefill_chunk`` set at construction compiles the
        chunk/mixed steps); the budget is read fresh each scheduling
        tick, and chunk lengths bucket to powers of two, so moving it
        between pow2 values costs at most one retrace per new bucket."""
        if self.max_prefill_chunk is None:
            raise ValueError(
                "engine was built without chunked prefill "
                "(max_prefill_chunk=None); the chunk step only compiles "
                "at construction")
        if n < 1:
            raise ValueError("max_prefill_chunk must be >= 1")
        if self._has_ssm and n % self.cfg.ssm.chunk_size != 0:
            raise ValueError(
                f"max_prefill_chunk={n} must stay a multiple of "
                f"cfg.ssm.chunk_size={self.cfg.ssm.chunk_size} (SSD "
                "chunk-boundary bit-identity)")
        self.max_prefill_chunk = int(n)

    def step(self) -> Dict[int, List[int]]:
        """Run ONE engine round (the async server's unit of work):
        bounded prefill + the round's decode, returning any requests
        that finished.  With ``decode_block_rounds=K`` a pure-decode
        step may burn up to K rounds in its one dispatch — still one
        bounded unit between two looks at the arrival queue."""
        return self.run(max_rounds=1)

    def run(self, max_rounds: int = 1000) -> Dict[int, List[int]]:
        """Engine rounds until done: every round runs (at most) one
        prefill step AND the fused decode round.  With chunking on
        (``max_prefill_chunk``), the prefill step is at most one fused
        chunk batch — bounded work — so in-flight decodes emit a token
        every round however long the arriving prompts are.  Without it,
        the prefill step drains the whole queue (monolithic batches):
        rounds where that overshoots the chunk budget while decodes
        waited are counted in ``stats["decode_stall_rounds"]``.

        Multi-round fusion hooks in here: a chunk batch coexisting with
        decodes runs as ONE mixed dispatch (``mixed_rounds``, the tick
        reports it already decoded), and with nothing to admit the
        engine burns up to ``decode_block_rounds`` rounds per dispatch
        in the persistent K-block loop — ``rounds`` advances by the
        rounds the block actually consumed, so ``max_rounds`` keeps its
        round-at-a-time meaning.  Each iteration is one ``engine.round``
        profiler span, so ``step()`` opens exactly one."""
        results: Dict[int, List[int]] = {}
        rounds = 0
        while ((self.queue or self._chunk_q or self.active)
               and rounds < max_rounds):
            with _span("engine.round"):
                rounds += self._round(results, max_rounds - rounds)
        return results

    def _round(self, results: Dict[int, List[int]], budget: int) -> int:
        """One iteration of ``run``; returns the rounds it consumed."""
        chunked = self.fused_prefill and self.max_prefill_chunk is not None
        had_active = bool(self.active)
        decoded = False
        if self.queue or self._chunk_q:
            if self.active:
                # overlap the pre-round CoW flush with prefill work:
                # reserve the decode tails NOW and dispatch the
                # coalesced copies, so forking workloads pay the
                # flush behind prefill host work (JAX dispatch is
                # async), not in front of the decode step
                with _span("engine.plan"):
                    self._reserve_tails(sorted(self.active))
                with _span("engine.flush"):
                    self.cache.queue.flush_overlapped(self.cache.lib.flush)
            if chunked:
                prefill_toks, decoded = self._prefill_tick(
                    allow_mixed=self._mixed_step is not None)
            else:
                prefill_toks = self._prefill_round()
            if (had_active and self.max_prefill_chunk is not None
                    and prefill_toks > self.max_prefill_chunk):
                # an un-chunked prefill blew the per-round budget
                # while decodes were in flight: they waited behind
                # it — the latency chunking bounds.  Never
                # increments when the chunked scheduler is on.
                self.stats["decode_stall_rounds"] += 1
            # a budget of 1 is satisfied by the prefill token alone:
            # retire those now instead of decoding a surplus token
            self._finish_done(results)
        elif self.active and self._block_step is not None:
            # pure decode, nothing to admit: one dispatch covers up
            # to K rounds (never past the caller's round budget)
            n = self._decode_block(budget)
            self._finish_done(results)
            return n
        if not decoded:
            self._decode_round()
        self._finish_done(results)
        return 1

    def _finish_done(self, results: Dict[int, List[int]]) -> None:
        with _span("engine.commit"):
            # mirror the cache's prefix-sharing counters (engine.stats
            # is the one stats surface servers/benches read)
            for key in ("prefix_hits", "prefix_hit_tokens",
                        "prefix_evictions", "state_pages", "state_forks",
                        "prefix_declined_ssm"):
                self.stats[key] = self.cache.stats[key]
            for rid in list(self.active):
                r = self.active[rid]
                hit_eos = (r.eos_token_id is not None and r.out_tokens
                           and r.out_tokens[-1] == r.eos_token_id)
                if len(r.out_tokens) >= r.max_new_tokens or hit_eos:
                    r.done = True
                    results[rid] = r.out_tokens
                    self.cache.free(rid)
                    del self.active[rid]
                    self._reserved_tails.discard(rid)

    # --------------------------- internals ----------------------------- #

    def _layer_params(self):
        return self.params["group0"]

    def _sharded_specs(self, n_args, arena_at):
        """in_specs for a shard_map-wrapped step: params (arg 0) follow
        the resolved spec tree, arenas split on the KV-head axis, and
        everything else — block tables, lengths, scatter plans, seeds,
        temperatures — is replicated."""
        specs = [P()] * n_args
        specs[0] = self._param_specs
        for i in arena_at:
            specs[i] = self._arena_spec
        return tuple(specs)

    def _shard_wrap(self, fn, n_args, arena_at, n_extra_out=1):
        """Wrap a fused step fn as a shard_map program over the mesh:
        one dispatch spanning every device.  Outputs are ``n_extra_out``
        replicated values (tokens and logits — identical on every shard,
        the final logit reduce and sampling run replicated) followed by
        the two sharded arenas and the (conv, ssm) state outputs — always None
        under a mesh (the constructor rejects mesh+SSM), so their P()
        specs map over zero leaves.  ``check_vma=False``: the
        collectives guarantee the replication the spec claims; jax's
        checker cannot see through the masked gathers."""
        out_specs = (P(),) * n_extra_out + (self._arena_spec,
                                            self._arena_spec, P(), P())
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=self._sharded_specs(n_args, arena_at),
                             out_specs=out_specs, check_vma=False)

    def _step_kwargs(self):
        kw = dict(use_pallas=self.use_pallas)
        if self.mesh is not None:
            kw.update(axis="model", compressed=self.compressed_collectives)
        return kw

    def _build_fused_step(self):
        """One jit covering forward + KV scatter + token selection.

        The Python body only runs when jax traces (cache miss), so the
        closure's counter bump is exactly a retrace counter.  Arenas are
        donated where the backend supports it (TPU/GPU) so the in-jit
        scatter is an in-place update.  With a mesh, the whole step runs
        as a shard_map program (constructed inside the traced body, so
        the retrace counter keeps its meaning).
        """
        eng = self

        # named so its device module reads jit_decode_step in a profile
        def decode_step(params, last, k_arena, v_arena, bt, lens, pages,
                        slots, seed, temps, conv_arena, ssm_arena, srows):
            eng.stats["jit_traces"] += 1
            fn = functools.partial(_fused_decode_step, eng.cfg, eng.pcfg,
                                   **eng._step_kwargs())
            if eng.mesh is not None:
                fn = eng._shard_wrap(fn, 13, (2, 3))
            return fn(params, last, k_arena, v_arena, bt, lens,
                      pages, slots, seed, temps, conv_arena, ssm_arena,
                      srows)

        donate = ((2, 3, 10, 11) if jax.default_backend() in ("tpu", "gpu")
                  else ())
        return jax.jit(decode_step, donate_argnums=donate)

    def _build_fused_prefill_step(self):
        """One jit covering the whole prefill batch: masked forward +
        in-jit KV scatter + first-token selection.  Retraces only per
        distinct (length-bucket, batch-bucket) pair; the closure's
        counter bump is exactly a retrace counter (the body only runs on
        a trace-cache miss)."""
        eng = self

        def prefill_step(params, toks, lens, k_arena, v_arena, pages, slots,
                         src, seed, temps, conv_arena, ssm_arena, srows,
                         has_writes):
            eng.stats["prefill_jit_traces"] += 1
            fn = functools.partial(_fused_prefill_step, eng.cfg, eng.pcfg,
                                   has_writes=has_writes,
                                   **eng._step_kwargs())
            if eng.mesh is not None:
                fn = eng._shard_wrap(fn, 13, (3, 4), n_extra_out=2)
            return fn(params, toks, lens, k_arena, v_arena,
                      pages, slots, src, seed, temps, conv_arena,
                      ssm_arena, srows)

        donate = ((3, 4, 10, 11) if jax.default_backend() in ("tpu", "gpu")
                  else ())
        return jax.jit(prefill_step, donate_argnums=donate,
                       static_argnames=("has_writes",))

    def _build_fused_chunk_step(self):
        """One jit covering a whole chunk batch: prefix-KV masked chunk
        forward + in-jit chunk scatter + token selection.  Retraces only
        per distinct (chunk-bucket, batch-bucket, table-width) triple —
        counted in the same ``stats["prefill_jit_traces"]`` as the
        monolithic prefill (the body only runs on a trace-cache miss)."""
        eng = self

        def chunk_step(params, toks, lens, offs, k_arena, v_arena, bt,
                       plens, pages, slots, src, seed, temps, conv_arena,
                       ssm_arena, srows, has_writes):
            eng.stats["prefill_jit_traces"] += 1
            fn = functools.partial(_fused_chunk_prefill_step, eng.cfg,
                                   eng.pcfg, has_writes=has_writes,
                                   **eng._step_kwargs())
            if eng.mesh is not None:
                fn = eng._shard_wrap(fn, 16, (4, 5), n_extra_out=2)
            return fn(params, toks, lens, offs, k_arena, v_arena, bt,
                      plens, pages, slots, src, seed, temps, conv_arena,
                      ssm_arena, srows)

        donate = ((4, 5, 13, 14) if jax.default_backend() in ("tpu", "gpu")
                  else ())
        return jax.jit(chunk_step, donate_argnums=donate,
                       static_argnames=("has_writes",))

    def _build_fused_block_step(self):
        """One jit covering up to K decode rounds (``lax.while_loop``):
        K forwards + K masked KV scatters + K token selections, one host
        transfer.  K is baked into the plan arrays' trailing dim, so a
        fixed ``decode_block_rounds`` retraces only per (batch-bucket,
        table-width) pair like the single-round step; the closure's
        counter bump is exactly a retrace counter."""
        eng = self

        def block_step(params, last, steps, k_arena, v_arena, bt, lens,
                       pages, slots, eos, seed, temps, rowmap, conv_arena,
                       ssm_arena, srows):
            eng.stats["block_jit_traces"] += 1
            fn = functools.partial(_fused_block_step, eng.cfg, eng.pcfg,
                                   **eng._step_kwargs())
            if eng.mesh is not None:
                fn = eng._shard_wrap(fn, 16, (3, 4))
            return fn(params, last, steps, k_arena, v_arena, bt, lens,
                      pages, slots, eos, seed, temps, rowmap, conv_arena,
                      ssm_arena, srows)

        donate = ((3, 4, 13, 14) if jax.default_backend() in ("tpu", "gpu")
                  else ())
        return jax.jit(block_step, donate_argnums=donate)

    def _build_fused_mixed_step(self):
        """One jit covering a whole mixed round: chunk batch forward +
        chunk scatter + first-token selection, THEN the decode round —
        whose inputs for rows finishing their prompt this round come
        straight from the chunk half (``d_from_chunk``), never touching
        the host.  Retraces per distinct (chunk, decode) operand-shape
        pair; counted separately so the single-path counters stay
        comparable oracles."""
        eng = self

        def mixed_step(params, c_toks, c_lens, c_offs, k_arena, v_arena,
                       c_bt, c_plens, c_pages, c_slots, c_src, c_seed,
                       c_temps, d_last, d_bt, d_lens, d_pages, d_slots,
                       d_seed, d_temps, d_from_chunk, conv_arena, ssm_arena,
                       c_srows, d_srows, has_writes):
            eng.stats["mixed_jit_traces"] += 1
            fn = functools.partial(_fused_mixed_step, eng.cfg, eng.pcfg,
                                   has_writes=has_writes,
                                   **eng._step_kwargs())
            if eng.mesh is not None:
                fn = eng._shard_wrap(fn, 25, (4, 5), n_extra_out=3)
            return fn(params, c_toks, c_lens, c_offs, k_arena, v_arena,
                      c_bt, c_plens, c_pages, c_slots, c_src, c_seed,
                      c_temps, d_last, d_bt, d_lens, d_pages, d_slots,
                      d_seed, d_temps, d_from_chunk, conv_arena,
                      ssm_arena, c_srows, d_srows)

        donate = ((4, 5, 21, 22) if jax.default_backend() in ("tpu", "gpu")
                  else ())
        return jax.jit(mixed_step, donate_argnums=donate,
                       static_argnames=("has_writes",))

    def _prefill_round(self) -> int:
        """Drain the request queue: one fused jitted dispatch per
        (length-bucket) prefill batch, or the eager per-request oracle
        with ``fused_prefill=False`` (exact parity for greedy requests;
        sampled requests consume the TRNG per batch vs per request, so
        their streams differ by construction).  Returns the prompt
        tokens processed (the round's prefill work, for stall
        accounting)."""
        reqs, self.queue = self.queue, []
        toks = sum(len(r.prompt) for r in reqs)
        if not self.fused_prefill:
            for r in reqs:
                self._prefill(r)
            return toks
        # create every sequence in submission order first, so shared
        # prefixes (`share_with`) resolve across bucket groups; tokens=
        # lets the radix prefix cache longest-prefix-match each prompt
        # against every previously COMMITTED prompt (a batch submitted
        # together can't hit on itself — inserts happen at commit)
        with _span("engine.admit"):
            for r in reqs:
                self.cache.create(r.req_id, len(r.prompt),
                                  share_with=r.share_with,
                                  shared_len=r.shared_len,
                                  tokens=r.prompt)
        groups: Dict[int, List[Request]] = {}
        for r in reqs:
            groups.setdefault(_bucket_pow2(len(r.prompt)), []).append(r)
        for sp in sorted(groups):
            self._prefill_batch_fused(groups[sp], sp)
        return toks

    # ---------------- chunked prefill (decode-interleaved) ------------- #

    def _prefill_tick(self, allow_mixed: bool = False):
        """One round's bounded prefill work under the chunked scheduler:
        admit newly queued requests to the chunk backlog, then dispatch
        at most ONE fused chunk batch — FIFO over the backlog, rows
        sharing one chunk-length bucket, at most ``max_prefill_chunk``
        real prompt tokens.  Unfinished prompts return to the backlog
        front (their next chunk leads the next round), so a long prompt
        streams across rounds while the decode round keeps dispatching
        every round.

        With ``allow_mixed`` and decode rows present (active sequences,
        or prompts finishing this very chunk), the chunk batch and the
        round's decode fuse into ONE dispatch (``_mixed_round``).
        Returns ``(prompt_tokens_processed, decoded)`` — ``decoded``
        tells the caller this round's decode already ran."""
        self._admit_queue()
        with _span("engine.plan"):
            batch, sc = self._select_chunk_batch()
        if not batch:
            return 0, False
        toks = sum(clen for _, clen in batch)
        if allow_mixed:
            fin = {st.req.req_id for st, clen in batch
                   if st.off + clen >= len(st.req.prompt)
                   and st.req.max_new_tokens > 1}
            d_rids = sorted(set(self.active) | fin)
            if d_rids:
                unfinished = self._mixed_round(batch, sc, d_rids)
                self._chunk_q = unfinished + self._chunk_q
                return toks, True
        unfinished = self._prefill_chunk_batch_fused(batch, sc)
        self._chunk_q = unfinished + self._chunk_q
        return toks, False

    def _select_chunk_batch(self):
        """Pick this round's chunk batch off the backlog: FIFO, one
        chunk-length bucket, within the round's token budget; states
        passed over (bucket mismatch, budget, unmet share dependency)
        stay queued in order.  Returns ``(batch, sc)`` — (state, len)
        pairs and their shared length bucket."""
        if not self._chunk_q:
            return [], None
        budget = self.max_prefill_chunk
        batch: List[tuple] = []          # (_ChunkPrefill, chunk_len)
        keep: List[_ChunkPrefill] = []
        sc = None                        # the batch's chunk-length bucket
        for st in self._chunk_q:
            if st.dep is not None:
                if not self._source_committed(st.dep, st.dep_len):
                    keep.append(st)      # shared pages not yet committed
                    continue
                st.dep = None            # satisfied once = satisfied forever
            clen = min(self.max_prefill_chunk, st.remaining)
            cb = _bucket_pow2(clen)
            if batch and (cb != sc or clen > budget):
                keep.append(st)
                continue
            sc = cb
            batch.append((st, clen))
            budget -= clen
        self._chunk_q = keep
        return batch, sc

    def _source_committed(self, src_id: Optional[int], n: int) -> bool:
        """Has sequence ``src_id`` committed at least ``n`` prompt
        tokens to the arena?  True when it is not mid-prefill (finished,
        or never chunked); sharers gate on this before reading shared
        pages."""
        if src_id is None:
            return True
        st = self._chunk_by_id.get(src_id)
        return st is None or st.off >= n

    def _admit_queue(self) -> None:
        """Create sequences for queued requests (submission order, so
        ``share_with`` resolves) and push them onto the chunk backlog.

        A prompt fully covered by a shared prefix has no KV of its own
        to commit: it becomes a single NO-WRITE chunk — the last prompt
        position recomputed against the committed prefix, scatter
        suppressed — gated until the source commits the whole prompt.
        That keeps even very long covered sharers inside the per-round
        chunk budget (a whole-prompt forward here would reintroduce the
        decode stall this scheduler exists to remove)."""
        reqs, self.queue = self.queue, []
        with _span("engine.admit"):
            for r in reqs:
                seq = self.cache.create(r.req_id, len(r.prompt),
                                        share_with=r.share_with,
                                        shared_len=r.shared_len,
                                        tokens=r.prompt)
                off = seq.shared_prefix_pages * self.cache.page_size
                n = len(r.prompt)
                if off >= n:
                    st = _ChunkPrefill(r, n - 1, dep=r.share_with,
                                       dep_len=n, write=False)
                else:
                    st = _ChunkPrefill(r, off, dep=r.share_with,
                                       dep_len=off)
                self._chunk_q.append(st)
                self._chunk_by_id[r.req_id] = st

    def _chunk_operands(self, batch: List[tuple], sc: int) -> dict:
        """Assemble a chunk batch's device operands + scatter plan
        (shared by the standalone chunk dispatch and the mixed round,
        which must plan AFTER reserving decode tails so CoW retargets
        are seen).  Pad rows duplicate row 0; pad scatter entries
        duplicate entry 0 (identical (page, slot, value) writes are a
        deterministic no-op); an all-no-write batch skips the scatter
        entirely (``has_writes=False``, its own trace)."""
        B = len(batch)
        Bp = _bucket_pow2(B)
        idx = list(range(B)) + [0] * (Bp - B)   # pad rows duplicate row 0
        toks = np.zeros((Bp, sc), np.int32)
        lens = np.zeros((Bp,), np.int32)
        offs = np.zeros((Bp,), np.int32)
        temps = np.zeros((Bp,), np.float32)
        for row, i in enumerate(idx):
            st, clen = batch[i]
            toks[row, :clen] = st.req.prompt[st.off:st.off + clen]
            lens[row] = clen
            offs[row] = st.off
            temps[row] = st.req.temperature
        # prefix block table over each sequence's FULL page list, valid
        # length = committed tokens: the width bucket is per-prompt
        # constant, so chunk count never forces a retrace
        rids = [batch[i][0].req.req_id for i in idx]
        bt, plens = self.cache.block_table(rids,
                                           lengths=[int(o) for o in offs])
        pages: List[int] = []
        slots: List[int] = []
        src: List[int] = []
        for i, (st, clen) in enumerate(batch):
            if not st.write:             # covered sharer: recompute only
                continue
            seq = self.cache.seqs[st.req.req_id]
            p_i, s_i = self.cache.prefill_scatter_plan(seq, start=st.off,
                                                       stop=st.off + clen)
            pages += p_i
            slots += s_i
            src += [i * sc + j for j in range(clen)]
        n_valid = len(pages)
        N = Bp * sc
        if n_valid:
            pages += [pages[0]] * (N - n_valid)
            slots += [slots[0]] * (N - n_valid)
            src += [src[0]] * (N - n_valid)
        else:
            pages = [0] * N
            slots = [0] * N
            src = [0] * N
        return {
            "toks": jnp.asarray(toks), "lens": jnp.asarray(lens),
            "offs": jnp.asarray(offs), "bt": bt, "plens": plens,
            "pages": jnp.asarray(pages, jnp.int32),
            "slots": jnp.asarray(slots, jnp.int32),
            "src": jnp.asarray(src, jnp.int32),
            "temps": jnp.asarray(temps),
            "plan_pages": pages[:n_valid], "plan_slots": slots[:n_valid],
            "n_valid": n_valid, "rids": rids,
        }

    def _finish_chunks(self, batch: List[tuple], tokens,
                       logits) -> List[_ChunkPrefill]:
        """Advance chunk offsets; rows whose chunk completed the prompt
        consume their first token (one lazy host transfer per batch) and
        join the active set.  Returns the still-unfinished states."""
        toks_np = None
        unfinished: List[_ChunkPrefill] = []
        with _span("engine.commit"):
            for i, (st, clen) in enumerate(batch):
                st.off += clen
                if st.remaining <= 0:
                    if toks_np is None:     # the batch's one host transfer
                        with _span("engine.sync"):
                            toks_np = np.asarray(tokens)
                    st.req.out_tokens.append(int(toks_np[i]))
                    _keep_logits(st.req, logits, i)
                    self.active[st.req.req_id] = st.req
                    self.stats["prefills"] += 1
                    del self._chunk_by_id[st.req.req_id]
                    # the prompt's full pages now hold real KV: index them
                    self.cache.commit_prefix(st.req.req_id, st.req.prompt)
                else:
                    unfinished.append(st)
        return unfinished

    def _prefill_chunk_batch_fused(self, batch: List[tuple],
                                   sc: int) -> List[_ChunkPrefill]:
        """One compiled dispatch for a same-bucket batch of prefill
        chunks: length-masked chunk forward with prefix-KV flash
        attention over each sequence's committed arena pages (gathered
        in-scan via the block table), in-jit chunk-KV scatter against
        the cache's per-chunk plan, in-jit token selection.  One host
        transfer per batch, consumed only by rows whose chunk completes
        the prompt.  Returns the still-unfinished chunk states."""
        # the step READS the arena (prefix gather): any pending backlog
        # must land first
        with _span("engine.flush"):
            self.cache.flush_pending()
        with _span("engine.plan"):
            c = self._chunk_operands(batch, sc)
            srows, conv, ssm = self._state_operands(c["rids"])
            self.rng_ctr += 1
            seed = self.rng_seed + jnp.uint32(self.rng_ctr)
        with _span("engine.dispatch.chunk"):
            tokens, logits, k_arena, v_arena, conv_a, ssm_a = \
                self._chunk_step(
                    self.params, c["toks"], c["lens"], c["offs"],
                    self.cache.k_arena, self.cache.v_arena, c["bt"],
                    c["plens"], c["pages"], c["slots"], c["src"], seed,
                    c["temps"], conv, ssm, srows,
                    has_writes=c["n_valid"] > 0 and self._has_attn)
        with _span("engine.commit"):
            # chunk scatters account as the fused_prefill kind, same as
            # the monolithic batch (PimOpQueue.launches_by_kind, trace
            # kv_writes)
            kv_plan = (c["plan_pages"], c["plan_slots"]) \
                if self._has_attn else ([], [])
            self.cache.commit_fused_prefill(k_arena, v_arena, *kv_plan)
            if self._has_ssm:
                self.cache.state.adopt(conv_a, ssm_a)
                self.cache.state.record_fused_write(
                    [st.req.req_id for st, _ in batch])
            self._note_chunk(batch, c)
            self.stats["fused_prefill_dispatches"] += 1
        return self._finish_chunks(batch, tokens, logits)

    def _mixed_round(self, batch: List[tuple], sc: int,
                     d_rids: List[int]) -> List[_ChunkPrefill]:
        """ONE compiled dispatch for a whole mixed round: the chunk
        batch AND the decode round (which today's sequential path pays
        two dispatches for).  The decode half covers every active
        sequence plus every prompt finishing in this very chunk batch —
        their first token never touches the host; ``d_from_chunk`` wires
        it from the chunk half's selection into the decode input in-jit.

        Bookkeeping: both commits run with ``kind=None`` and the round
        is accounted as ONE ``fused_mixed`` launch; the rng counter
        advances twice (chunk seed, then decode seed), matching the
        sequential two-dispatch schedule, so sampled streams are
        unchanged by the fusion.  A finishing row whose FIRST token
        turns out to be its EOS has its decode token discarded host-side
        (the speculative KV write beyond its committed length dies with
        the sequence's pages — ``free`` zeroes them).  Returns the
        still-unfinished chunk states."""
        fin = {st.req.req_id: st.req for st, clen in batch
               if st.off + clen >= len(st.req.prompt)}
        reqmap = dict(self.active)
        reqmap.update(fin)
        # reserve every decode row's tail BEFORE planning the chunk
        # scatter: a CoW retarget must be seen by the plan, and the
        # coalesced copies must land before the step reads the arena
        with _span("engine.plan"):
            self._reserve_tails(d_rids)
            self._reserved_tails.clear()
        with _span("engine.flush"):
            self.cache.flush_pending()
        with _span("engine.plan"):
            c = self._chunk_operands(batch, sc)
            row_of = {st.req.req_id: i for i, (st, _) in enumerate(batch)}
            B = len(d_rids)
            Bp = _bucket_pow2(B)
            idx = list(range(B)) + [0] * (Bp - B)   # pad rows duplicate row 0
            seqs = [self.cache.seqs[d_rids[i]] for i in idx]
            d_last = np.zeros((Bp,), np.int32)
            d_from = np.full((Bp,), -1, np.int32)
            d_temps = np.zeros((Bp,), np.float32)
            for row, i in enumerate(idx):
                rid = d_rids[i]
                r = reqmap[rid]
                d_temps[row] = r.temperature
                if rid in fin:               # token arrives in-jit
                    d_from[row] = row_of[rid]
                else:
                    d_last[row] = r.out_tokens[-1]
            d_pages = np.asarray([s.pages[-1] for s in seqs], np.int32)
            d_slots = np.asarray([s.length % self.cache.page_size
                                  for s in seqs], np.int32)
            d_bt, d_lens = self.cache.block_table([d_rids[i] for i in idx])
            self._note_decode_kv(d_rids, [s.length for s in seqs],
                                 d_bt.shape[1])
            c_srows, conv, ssm = self._state_operands(c["rids"])
            d_srows, _, _ = self._state_operands([d_rids[i] for i in idx])
            self.rng_ctr += 1
            c_seed = self.rng_seed + jnp.uint32(self.rng_ctr)
            self.rng_ctr += 1
            d_seed = self.rng_seed + jnp.uint32(self.rng_ctr)
            d_last, d_pages, d_slots, d_temps, d_from = (
                jnp.asarray(x) for x in (d_last, d_pages, d_slots, d_temps,
                                         d_from))
        with _span("engine.dispatch.mixed"):
            (c_tokens, c_logits, d_tokens, k_arena, v_arena, conv_a,
             ssm_a) = self._mixed_step(
                self.params, c["toks"], c["lens"], c["offs"],
                self.cache.k_arena, self.cache.v_arena, c["bt"],
                c["plens"], c["pages"], c["slots"], c["src"], c_seed,
                c["temps"], d_last, d_bt, d_lens, d_pages, d_slots, d_seed,
                d_temps, d_from, conv, ssm, c_srows, d_srows,
                has_writes=c["n_valid"] > 0 and self._has_attn)
        with _span("engine.commit"):
            kv_plan = (c["plan_pages"], c["plan_slots"]) \
                if self._has_attn else ([], [])
            self.cache.commit_fused_prefill(k_arena, v_arena, *kv_plan,
                                            kind=None)
            self.cache.commit_fused_round(d_rids, k_arena, v_arena,
                                          kind=None, wrote_kv=self._has_attn)
            if self._has_ssm:
                self.cache.state.adopt(conv_a, ssm_a)
                # trace both halves' state writes: the chunk rows'
                # prefill state and the decode rows' round state (one
                # fused launch)
                self.cache.state.record_fused_write(
                    [st.req.req_id for st, _ in batch])
                self.cache.state.record_fused_write(d_rids)
            # the whole round — chunk scatter included — was ONE launch
            self.cache.queue.count_external("fused_mixed")
            self._note_chunk(batch, c)
            self.stats["mixed_dispatches"] += 1
        unfinished = self._finish_chunks(batch, c_tokens, c_logits)
        with _span("engine.sync"):
            d_toks = np.asarray(d_tokens)[:B]
        with _span("engine.commit"):
            emitted = 0
            for i, rid in enumerate(d_rids):
                r = reqmap[rid]
                if (rid in fin and r.eos_token_id is not None
                        and r.out_tokens[-1] == r.eos_token_id):
                    continue   # first token was EOS: decode token is dead
                r.out_tokens.append(int(d_toks[i]))
                emitted += 1
            self.stats["decode_rounds"] += 1
            self.stats["tokens_out"] += emitted
        return unfinished

    def _prefill_batch_fused(self, reqs: List[Request], sp: int) -> None:
        """One compiled dispatch for a same-length-bucket prefill batch;
        one host transfer (the batch's first tokens)."""
        B = len(reqs)
        with _span("engine.plan"):
            toks, lens, temps, pages, slots, src, plan = \
                self._prefill_operands(reqs, sp)
        # the step reads the arena (shared-prefix gathers) — any backlog
        # (e.g. prefix-cache eviction inits from create-time pressure)
        # must land first
        with _span("engine.flush"):
            self.cache.flush_pending()
        with _span("engine.plan"):
            idx = list(range(B)) + [0] * (toks.shape[0] - B)
            srows, conv, ssm = self._state_operands(
                [reqs[i].req_id for i in idx])
            self.rng_ctr += 1
            seed = self.rng_seed + jnp.uint32(self.rng_ctr)
            toks, lens, temps = (jnp.asarray(x) for x in (toks, lens, temps))
        with _span("engine.dispatch.prefill"):
            tokens, logits, k_arena, v_arena, conv_a, ssm_a = \
                self._prefill_step(
                    self.params, toks, lens, self.cache.k_arena,
                    self.cache.v_arena, pages, slots, src, seed, temps,
                    conv, ssm, srows,
                    has_writes=bool(plan[0]) and self._has_attn)
        with _span("engine.commit"):
            kv_plan = plan if self._has_attn else ([], [])
            self.cache.commit_fused_prefill(k_arena, v_arena, *kv_plan)
            if self._has_ssm:
                self.cache.state.adopt(conv_a, ssm_a)
                self.cache.state.record_fused_write(
                    [r.req_id for r in reqs])
        with _span("engine.sync"):
            toks_np = np.asarray(tokens)[:B]   # the batch's one transfer
        with _span("engine.commit"):
            for i, r in enumerate(reqs):
                r.out_tokens.append(int(toks_np[i]))
                _keep_logits(r, logits, i)
                self.active[r.req_id] = r
                self.stats["prefills"] += 1
                self.cache.commit_prefix(r.req_id, r.prompt)
            self.stats["fused_prefill_dispatches"] += 1

    def _prefill_operands(self, reqs: List[Request], sp: int):
        """A prefill batch's host operands: padded tokens, lengths and
        temperatures (numpy), the scatter plan padded to ``Bp * sp``
        entries (``pages``, ``slots``, ``src`` on the device), and the
        plan's valid ``(pages, slots)``."""
        B = len(reqs)
        Bp = _bucket_pow2(B)
        idx = list(range(B)) + [0] * (Bp - B)   # pad rows duplicate req 0
        toks = np.zeros((Bp, sp), np.int32)
        lens = np.zeros((Bp,), np.int32)
        temps = np.zeros((Bp,), np.float32)
        for row, i in enumerate(idx):
            r = reqs[i]
            toks[row, :len(r.prompt)] = r.prompt
            lens[row] = len(r.prompt)
            temps[row] = r.temperature
        # host-side arena-destination plan: (page, slot) per prompt token
        # the batch must write, plus the flat (row*sp + pos) source index
        # into the forward's stacked K/V output
        pages: List[int] = []
        slots: List[int] = []
        src: List[int] = []
        for i, r in enumerate(reqs):
            seq = self.cache.seqs[r.req_id]
            start = seq.shared_prefix_pages * self.cache.page_size
            p_i, s_i = self.cache.prefill_scatter_plan(seq, start=start)
            pages += p_i
            slots += s_i
            src += [i * sp + pos for pos in range(start, seq.length)]
        n_valid = len(pages)
        N = Bp * sp
        if n_valid:
            # pad entries duplicate entry 0: identical (page, slot,
            # value) writes are a deterministic no-op — the same trick
            # the decode round plays with pad rows
            pages += [pages[0]] * (N - n_valid)
            slots += [slots[0]] * (N - n_valid)
            src += [src[0]] * (N - n_valid)
        else:
            # batch fully covered by shared prefixes: nothing to write —
            # has_writes=False skips the scatter inside the jit (its own
            # trace, but the normal path never pays a no-op gather)
            pages = [0] * N
            slots = [0] * N
            src = [0] * N
        return (toks, lens, temps, jnp.asarray(pages, jnp.int32),
                jnp.asarray(slots, jnp.int32), jnp.asarray(src, jnp.int32),
                (pages[:n_valid], slots[:n_valid]))

    def _prefill(self, req: Request) -> None:
        """Eager per-request prefill — the fused path's parity oracle:
        un-jitted dense ``T.forward`` (a fresh XLA trace per distinct
        prompt length) plus host-side coalesced KV writes."""
        cfg, p = self.cfg, self.params
        toks = jnp.asarray(req.prompt, jnp.int32)[None]
        seq = self.cache.create(req.req_id, len(req.prompt),
                                share_with=req.share_with,
                                shared_len=req.shared_len,
                                tokens=req.prompt)
        start = seq.shared_prefix_pages * self.cache.page_size
        # full prefill forward (dense prefill math), then write kv pages
        max_len = len(req.prompt)
        cache = T.init_cache(cfg, 1, max_len)
        logits, dense_cache, _ = T.forward(
            cfg, self.pcfg, p, {"tokens": toks}, mode="prefill", cache=cache,
            lengths=jnp.asarray([max_len], jnp.int32))
        g = dense_cache["group0"]
        # g: {i_attn: (k,v)} stacked (L, 1, S, kvh, hd) for attention
        # sublayers; {i_mamba: (conv, ssm)} final recurrent state for
        # SSM sublayers ((G, 1, W-1, ch) / (G, 1, h, p, n))
        for key in (k for k in g if k.endswith("_attn")):
            k, v = g[key]
            self.cache.write_prompt_kv(seq, k[:, 0][:, start:max_len],
                                       v[:, 0][:, start:max_len], start=start)
        mamba_keys = sorted((k for k in g if k.endswith("_mamba")),
                            key=lambda s: int(s.split("_")[0]))
        if mamba_keys:
            conv = jnp.stack([g[k][0] for k in mamba_keys], axis=1)
            ssm = jnp.stack([g[k][1] for k in mamba_keys], axis=1)
            st = self.cache.state
            self.cache.queue.count_external(
                "eager_ssm_layer", st.conv.shape[0] * st.conv.shape[1])
            st.write([req.req_id], conv, ssm)
        tok = self._sample(logits[:, -1], req.temperature)
        req.out_tokens.append(int(tok[0]))
        _keep_logits(req, logits[:, -1], 0)
        self.active[req.req_id] = req
        self.stats["prefills"] += 1
        self.cache.commit_prefix(req.req_id, req.prompt)

    def _state_operands(self, rids_padded: List[int]):
        """The fused steps' state-arena operands for a (padded) row
        list: (srows, conv, ssm) — or three ``None``s on a dense engine
        (None is an empty pytree, so it threads through jit, donation,
        scan xs, and shard_map specs with zero leaves)."""
        if not self._has_ssm:
            return None, None, None
        st = self.cache.state
        srows = jnp.asarray(st.rows_for(rids_padded), jnp.int32)
        return srows, st.conv, st.ssm

    def _reserve_tails(self, rids: List[int]) -> None:
        """Reserve the incoming token's slot on every sequence in
        ``rids`` exactly once per round (CoW-copies shared tails,
        allocates boundary pages); idempotent within a round so the
        pre-prefill overlap path and the decode round compose."""
        for r in rids:
            if r not in self._reserved_tails:
                self.cache.ensure_writable_tail(self.cache.seqs[r])
                self._reserved_tails.add(r)

    def _decode_round(self) -> None:
        if not self.active:
            return
        rids = sorted(self.active)
        # reserve the slot for the incoming token on every sequence; the
        # CoW copies all land in ONE batched launch before attention reads
        # the arena (constant dispatch count, however many sequences fork)
        with _span("engine.plan"):
            self._reserve_tails(rids)
            self._reserved_tails.clear()
        with _span("engine.flush"):
            self.cache.flush_pending()
        if self.fused:
            toks = self._decode_round_fused(rids)
        else:
            toks = self._decode_round_eager(rids)
        with _span("engine.commit"):
            for i, r in enumerate(rids):
                self.active[r].out_tokens.append(int(toks[i]))
            self.stats["decode_rounds"] += 1
            self.stats["tokens_out"] += len(rids)

    def _decode_round_fused(self, rids: List[int]) -> np.ndarray:
        """One compiled dispatch for the whole round; one host transfer."""
        with _span("engine.plan"):
            B = len(rids)
            Bp = _bucket_pow2(B)
            # batch bucketing: pad rows duplicate sequence 0 — the
            # duplicate attention is wasted compute, and the duplicate
            # scatter writes the *same* values to the *same* (page,
            # slot), so it is a no-op
            idx = list(range(B)) + [0] * (Bp - B)
            seqs = [self.cache.seqs[rids[i]] for i in idx]
            last = np.asarray([[self.active[rids[i]].out_tokens[-1]]
                               for i in idx], np.int32)
            temps = np.asarray([self.active[rids[i]].temperature
                                for i in idx], np.float32)
            pages = np.asarray([s.pages[-1] for s in seqs], np.int32)
            slots = np.asarray([s.length % self.cache.page_size
                                for s in seqs], np.int32)
            bt, lens = self.cache.block_table([rids[i] for i in idx])
            self._note_decode_kv(rids, [s.length for s in seqs],
                                 bt.shape[1])
            srows, conv, ssm = self._state_operands([rids[i] for i in idx])
            self.rng_ctr += 1
            seed = self.rng_seed + jnp.uint32(self.rng_ctr)
            last, pages, slots, temps = (
                jnp.asarray(x) for x in (last, pages, slots, temps))
        with _span("engine.dispatch.decode"):
            tokens, k_arena, v_arena, conv_a, ssm_a = self._step(
                self.params, last, self.cache.k_arena, self.cache.v_arena,
                bt, lens, pages, slots, seed, temps, conv, ssm, srows)
        with _span("engine.commit"):
            self.cache.commit_fused_round(rids, k_arena, v_arena,
                                          wrote_kv=self._has_attn)
            if self._has_ssm:
                self.cache.state.adopt(conv_a, ssm_a)
                self.cache.state.record_fused_write(rids)
            # per-engine count: the queue's fused_decode counter is global
            # to the (possibly shared) lib, this one is this engine's own
            self.stats["fused_dispatches"] += 1
        with _span("engine.sync"):
            return np.asarray(tokens)[:B]  # the round's one host transfer

    def _note_decode_kv(self, rids: List[int], row_lens: List[int],
                        width: int) -> None:
        """Pad counters of one decode launch over ``rids``, whose padded
        rows have lengths ``row_lens``: the arena tokens the real rows
        attend over, against the token slots the launch reads
        (:func:`pa_ops.kv_slots_read`)."""
        self.stats["decode_kv_tokens"] += sum(self.cache.seqs[r].length
                                              for r in rids)
        self.stats["decode_kv_slots"] += pa_ops.kv_slots_read(
            row_lens, width, self.cache.page_size, self.use_pallas)

    def _note_chunk(self, batch: List[tuple], c: dict) -> None:
        """Pad counters of one chunk launch: its real chunk tokens
        against rows x chunk bucket."""
        self.stats["prefill_chunks"] += len(batch)
        self.stats["chunk_tokens"] += sum(clen for _, clen in batch)
        self.stats["chunk_token_slots"] += c["toks"].size

    def _decode_block(self, max_allowed: int) -> int:
        """Up to ``decode_block_rounds`` decode rounds in ONE dispatch —
        the persistent ``lax.while_loop`` inner loop, entered only when
        no admissions are pending.  Returns the rounds actually consumed
        (the longest row's emitted-token count), never more than
        ``max_allowed``.

        Host side: reserve each row's whole token block up front
        (``reserve_tokens`` — CoW + page allocation, one coalesced
        flush), build a (row, round) -> (page, slot) plan over the
        reserved pages, dispatch, then read the block's ONE host
        transfer and replay the device's stop rule (-1 sentinel = row
        already stopped; EOS stops after its own round).  Device side:
        the loop carries lengths/last-token/alive flags; a stopped row's
        scatter writes its slot's current value back (structural no-op),
        so the arena is bit-identical to a round-at-a-time run.  Plan
        arrays are always K wide (budget-short rows clamp to their last
        reserved slot), so a fixed K never retraces on workload
        stragglers."""
        rids = sorted(self.active)
        K = self.decode_block_rounds
        steps = [min(max_allowed, K,
                     self.active[r].max_new_tokens
                     - len(self.active[r].out_tokens))
                 for r in rids]
        if max(steps) <= 1:
            self._decode_round()
            return 1
        with _span("engine.plan"):
            for r, n in zip(rids, steps):
                self.cache.reserve_tokens(self.cache.seqs[r], n)
            self._reserved_tails.clear()
        with _span("engine.flush"):
            self.cache.flush_pending()
        with _span("engine.plan"):
            B = len(rids)
            Bp = _bucket_pow2(B)
            idx = list(range(B)) + [0] * (Bp - B)   # pad rows duplicate row 0
            ps = self.cache.page_size
            pages = np.zeros((Bp, K), np.int32)
            slots = np.zeros((Bp, K), np.int32)
            last = np.zeros((Bp,), np.int32)
            steps_arr = np.zeros((Bp,), np.int32)
            eos = np.full((Bp,), -1, np.int32)
            temps = np.zeros((Bp,), np.float32)
            for row, i in enumerate(idx):
                r = rids[i]
                req, seq, n = self.active[r], self.cache.seqs[r], steps[i]
                for t in range(K):
                    pos = seq.length + min(t, n - 1)
                    pages[row, t] = seq.pages[pos // ps]
                    slots[row, t] = pos % ps
                last[row] = req.out_tokens[-1]
                steps_arr[row] = n
                if req.eos_token_id is not None:
                    eos[row] = req.eos_token_id
                temps[row] = req.temperature
            # table spans the reserved pages (block_table covers the full
            # page list); lens stay the committed lengths — the loop
            # carries them forward round by round
            bt, lens = self.cache.block_table([rids[i] for i in idx])
            base = [self.cache.seqs[r].length for r in rids]
            # K sequential rounds consume K seeds: pass round 0's, the
            # loop derives round t's by offset — the same stream a
            # round-at-a-time run would draw
            self.rng_ctr += K
            seed = self.rng_seed + jnp.uint32(self.rng_ctr - K + 1)
            srows, conv, ssm = self._state_operands([rids[i] for i in idx])
            last, steps_arr, pages, slots, eos, temps = (
                jnp.asarray(x) for x in (last, steps_arr, pages, slots, eos,
                                         temps))
            rowmap = jnp.asarray(idx, dtype=jnp.int32)
        with _span("engine.dispatch.block"):
            tokens, k_arena, v_arena, conv_a, ssm_a = self._block_step(
                self.params, last, steps_arr, self.cache.k_arena,
                self.cache.v_arena, bt, lens, pages, slots, eos, seed, temps,
                rowmap, conv, ssm, srows)
        with _span("engine.sync"):
            toks_np = np.asarray(tokens)[:B]   # the block's ONE transfer
        with _span("engine.commit"):
            counts = []
            for i, r in enumerate(rids):
                req = self.active[r]
                n_i = 0
                for t in range(steps[i]):
                    tok = int(toks_np[i, t])
                    if tok < 0:            # device stopped this row earlier
                        break
                    req.out_tokens.append(tok)
                    n_i += 1
                    if (req.eos_token_id is not None
                            and tok == req.eos_token_id):
                        break
                counts.append(n_i)
            consumed = max(counts)
            self.cache.commit_fused_block(rids, counts, k_arena, v_arena,
                                          rounds=consumed,
                                          wrote_kv=self._has_attn)
            if self._has_ssm:
                self.cache.state.adopt(conv_a, ssm_a)
                self.cache.state.record_fused_write(rids, rounds=consumed)
            # each of the loop's rounds reads every padded row; a row
            # attends over its growing length while alive
            self.stats["decode_kv_tokens"] += sum(
                n * L + n * (n - 1) // 2 for L, n in zip(base, counts))
            self.stats["decode_kv_slots"] += sum(
                pa_ops.kv_slots_read(
                    [base[i] + min(t, counts[i]) for i in idx],
                    bt.shape[1], ps, self.use_pallas)
                for t in range(consumed))
            self.stats["decode_rounds"] += consumed
            self.stats["tokens_out"] += sum(counts)
            self.stats["multi_round_blocks"] += 1
        return consumed

    def _decode_round_eager(self, rids: List[int]) -> np.ndarray:
        """Pre-fusion baseline: Python layer loop, separate scatter."""
        last = jnp.asarray([[self.active[r].out_tokens[-1]] for r in rids],
                           jnp.int32)
        bt, lens = self.cache.block_table(rids)
        srows, conv_arena, ssm_arena = self._state_operands(rids)
        logits, k_new, v_new, conv_new, ssm_new = _eager_decode_forward(
            self.cfg, self.pcfg, self.params, last, self.cache.k_arena,
            self.cache.v_arena, bt, lens, use_pallas=self.use_pallas,
            conv_arena=conv_arena,
            ssm_arena=ssm_arena, srows=srows)
        if self._has_attn:
            # account the per-layer jitted paged-attention dispatches
            # (the O(num_layers) launches fusion removes) so
            # fused-vs-eager dispatch comparisons measure the real gap
            self.cache.queue.count_external("eager_attn_layer",
                                            self.cache.n_layers)
            # scatter the whole round's new KV (all layers, all
            # sequences) in one coalesced launch per arena
            self.cache.write_token_kv_batch(rids, k_new[:, :, 0],
                                            v_new[:, :, 0])
        else:
            # pure-SSM round: no KV write advances lengths, but the
            # block tables / reserved pages still track token count
            for r in rids:
                self.cache.seqs[r].length += 1
        if self._has_ssm:
            st = self.cache.state
            # per-layer eager SSM launches (what the fused scan removes)
            self.cache.queue.count_external(
                "eager_ssm_layer", st.conv.shape[0] * st.conv.shape[1])
            # ONE coalesced ssm_state_write flush for the whole round —
            # the SSM_STATE_WRITE opcode's JAX face, constant in depth
            # and batch
            st.write(rids, conv_new, ssm_new)
        temps = jnp.asarray([self.active[r].temperature for r in rids],
                            jnp.float32)
        self.rng_ctr += 1
        seed = self.rng_seed + jnp.uint32(self.rng_ctr)
        toks = _select_tokens(logits[:, 0], temps, seed,
                              use_pallas=self.use_pallas)
        return np.asarray(toks)            # one host transfer

    def _sample(self, logits: jax.Array, temperature: float) -> np.ndarray:
        """Eager-prefill sampling: delegates to ``_select_tokens`` — the
        same helper the fused prefill/decode steps trace in-jit — so the
        greedy/inverse-CDF choice has exactly one implementation."""
        self.rng_ctr += 1
        seed = self.rng_seed + jnp.uint32(self.rng_ctr)
        temps = jnp.full((logits.shape[0],), temperature, jnp.float32)
        return np.asarray(_select_tokens(logits, temps, seed,
                                         use_pallas=self.use_pallas))


# ---------------------------------------------------------------------- #
# Fused decode step (traced under one jax.jit per engine)
# ---------------------------------------------------------------------- #


def _fused_decode_step(cfg, pcfg, params, last, k_arena, v_arena, bt, lens,
                       pages, slots, seed, temps, conv_arena=None,
                       ssm_arena=None, srows=None, *, use_pallas: bool,
                       interpret: Optional[bool] = None,
                       axis: Optional[str] = None,
                       compressed: bool = False):
    """Forward (scan over layers) + KV scatter + token selection: the
    whole decode round as one compiled program over donated arenas.
    SSM state scatters inside the forward's scan (zero extra launches);
    a pure-SSM round (no attn sublayer) skips the KV scatter entirely.
    With ``axis`` (inside shard_map) the forward is tensor-parallel and
    the scatter writes each shard's local head slice."""
    logits, k_new, v_new, conv_arena, ssm_arena = _paged_decode_forward(
        cfg, pcfg, params, last, k_arena, v_arena, bt, lens,
        use_pallas=use_pallas, interpret=interpret, axis=axis,
        compressed=compressed, conv_arena=conv_arena,
        ssm_arena=ssm_arena, srows=srows)
    if k_new is not None:
        with jax.named_scope("kv_scatter"):
            k_arena = rc_ops.kv_scatter_inline(
                k_arena, pages, slots, k_new[:, :, 0].astype(k_arena.dtype),
                use_pallas=use_pallas, interpret=interpret)
            v_arena = rc_ops.kv_scatter_inline(
                v_arena, pages, slots, v_new[:, :, 0].astype(v_arena.dtype),
                use_pallas=use_pallas, interpret=interpret)
    tokens = _select_tokens(logits[:, 0], temps, seed,
                            use_pallas=use_pallas, interpret=interpret)
    return tokens, k_arena, v_arena, conv_arena, ssm_arena


# ---------------------------------------------------------------------- #
# Fused multi-round decode block (persistent lax.while_loop inner loop)
# ---------------------------------------------------------------------- #


def _fused_block_step(cfg, pcfg, params, last, steps, k_arena, v_arena, bt,
                      lens, pages, slots, eos, seed, temps, rowmap,
                      conv_arena=None, ssm_arena=None, srows=None, *,
                      use_pallas: bool,
                      interpret: Optional[bool] = None,
                      axis: Optional[str] = None, compressed: bool = False):
    """Up to K decode rounds as ONE compiled program: a ``while_loop``
    whose carry holds the per-row state a round-at-a-time host loop
    would bounce through Python — current lengths, last tokens, alive
    flags — plus the donated arenas.

    Per round ``t``: forward at the carried lengths, a MASKED KV scatter
    (dead rows re-write their slot's current value via
    ``kv_gather_inline``, keeping the scatter a structural no-op and the
    arena bit-identical to sequential rounds), token selection at
    ``seed + t`` (the seed a sequential round would draw), then the stop
    rule — a row dies when it has emitted its ``steps`` quota or its EOS
    token.  Emitted tokens land in a (B, K) buffer, ``-1`` marking
    rounds after a row stopped; the loop exits early once every row is
    dead, so an all-EOS round costs no further forwards.  ``rowmap``
    folds pad rows onto row 0's sampled draw so duplicate scatter
    destinations always carry identical values, sampled or greedy.
    SSM state arenas ride the carry; a dead row's in-scan state scatter
    writes its current value back (``alive`` masking inside
    :func:`_run_kinds`), the state analogue of the masked KV write-back.
    """
    K = pages.shape[1]

    def cond(carry):
        t, alive = carry[0], carry[1]
        return (t < K) & jnp.any(alive)

    def body(carry):
        (t, alive, lens, last, toks, k_arena, v_arena, conv_arena,
         ssm_arena) = carry
        logits, k_new, v_new, conv_arena, ssm_arena = _paged_decode_forward(
            cfg, pcfg, params, last[:, None], k_arena, v_arena, bt, lens,
            use_pallas=use_pallas, interpret=interpret, axis=axis,
            compressed=compressed, conv_arena=conv_arena,
            ssm_arena=ssm_arena, srows=srows, alive=alive)
        p_t = jax.lax.dynamic_index_in_dim(pages, t, axis=1, keepdims=False)
        s_t = jax.lax.dynamic_index_in_dim(slots, t, axis=1, keepdims=False)

        @jax.named_scope("kv_scatter")
        def masked_scatter(arena, new):
            old = rc_ops.kv_gather_inline(arena, p_t, s_t)
            val = jnp.where(alive[None, :, None, None],
                            new.astype(arena.dtype), old)
            return rc_ops.kv_scatter_inline(arena, p_t, s_t, val,
                                            use_pallas=use_pallas,
                                            interpret=interpret)

        if k_new is not None:
            k_arena = masked_scatter(k_arena, k_new[:, :, 0])
            v_arena = masked_scatter(v_arena, v_new[:, :, 0])
        raw = _select_tokens(logits[:, 0], temps,
                             seed + t.astype(jnp.uint32),
                             use_pallas=use_pallas, interpret=interpret,
                             rowmap=rowmap)
        toks = jax.lax.dynamic_update_slice(
            toks, jnp.where(alive, raw, -1)[:, None], (0, t))
        lens = lens + alive.astype(lens.dtype)
        last = jnp.where(alive, raw, last)
        hit_eos = alive & (eos >= 0) & (raw == eos)
        alive = alive & ((t + 1) < steps) & ~hit_eos
        return (t + 1, alive, lens, last, toks, k_arena, v_arena,
                conv_arena, ssm_arena)

    Bp = last.shape[0]
    carry = (jnp.int32(0), steps > 0, lens, last,
             jnp.full((Bp, K), -1, jnp.int32), k_arena, v_arena,
             conv_arena, ssm_arena)
    out = jax.lax.while_loop(cond, body, carry)
    _, _, _, _, toks, k_arena, v_arena, conv_arena, ssm_arena = out
    return toks, k_arena, v_arena, conv_arena, ssm_arena


# ---------------------------------------------------------------------- #
# Fused mixed round (one chunk batch + one decode round, one dispatch)
# ---------------------------------------------------------------------- #


def _fused_mixed_step(cfg, pcfg, params, c_toks, c_lens, c_offs, k_arena,
                      v_arena, c_bt, c_plens, c_pages, c_slots, c_src,
                      c_seed, c_temps, d_last, d_bt, d_lens, d_pages,
                      d_slots, d_seed, d_temps, d_from_chunk,
                      conv_arena=None, ssm_arena=None, c_srows=None,
                      d_srows=None, *, has_writes: bool, use_pallas: bool,
                      interpret: Optional[bool] = None,
                      axis: Optional[str] = None,
                      compressed: bool = False):
    """A whole mixed round as one compiled program: the chunk half runs
    first (its scatter is traced before the decode forward, so a prompt
    finishing this round decodes against its own just-written KV — the
    data dependency that makes XLA sequence the halves correctly on
    donated arenas), then the decode half, whose input token for rows
    with ``d_from_chunk[j] >= 0`` comes from the chunk half's selection
    instead of the host-supplied ``d_last``.  The state arenas thread
    chunk half -> decode half the same way: a prompt finishing this
    round decodes from its own just-scattered recurrent state."""
    c_tokens, c_logits, k_arena, v_arena, conv_arena, ssm_arena = \
        _fused_chunk_prefill_step(
            cfg, pcfg, params, c_toks, c_lens, c_offs, k_arena, v_arena,
            c_bt, c_plens, c_pages, c_slots, c_src, c_seed, c_temps,
            conv_arena, ssm_arena, c_srows, has_writes=has_writes,
            use_pallas=use_pallas, interpret=interpret, axis=axis,
            compressed=compressed)
    last = jnp.where(d_from_chunk >= 0,
                     c_tokens[jnp.clip(d_from_chunk, 0, None)], d_last)
    d_tokens, k_arena, v_arena, conv_arena, ssm_arena = _fused_decode_step(
        cfg, pcfg, params, last[:, None], k_arena, v_arena, d_bt, d_lens,
        d_pages, d_slots, d_seed, d_temps, conv_arena, ssm_arena, d_srows,
        use_pallas=use_pallas, interpret=interpret, axis=axis,
        compressed=compressed)
    return (c_tokens, c_logits, d_tokens, k_arena, v_arena, conv_arena,
            ssm_arena)


# ---------------------------------------------------------------------- #
# Fused bucketed prefill step (traced once per (length, batch) bucket)
# ---------------------------------------------------------------------- #


def _fused_prefill_step(cfg, pcfg, params, toks, lens, k_arena, v_arena,
                        pages, slots, src, seed, temps, conv_arena=None,
                        ssm_arena=None, srows=None, *,
                        has_writes: bool, use_pallas: bool,
                        interpret: Optional[bool] = None,
                        axis: Optional[str] = None,
                        compressed: bool = False):
    """Masked prefill forward + in-jit KV scatter + first-token
    selection: a whole prefill batch as one compiled program over
    donated arenas.

    ``pages``/``slots``/``src`` are the host-side scatter plan (length
    ``B*S`` flat entries): entry ``n`` writes the forward's stacked K/V
    at flat source index ``src[n]`` to ``arena[:, pages[n], slots[n]]``
    (pad entries duplicate entry 0 — identical writes, a deterministic
    no-op).  ``has_writes=False`` (static: the all-shared-prefix batch,
    or a pure-SSM engine with no KV to write) skips the scatter
    entirely; SSM state scatters inside the forward's scan.  Returns the
    tokens, the (B, V) logits they were chosen from, then the arenas.
    """
    logits, k_all, v_all, conv_arena, ssm_arena = _prefill_forward(
        cfg, pcfg, params, toks, lens, use_pallas=use_pallas,
        interpret=interpret, axis=axis, compressed=compressed,
        conv_arena=conv_arena, ssm_arena=ssm_arena, srows=srows)
    Bp, Sp = toks.shape

    @jax.named_scope("kv_scatter")
    def scatter(arena, new_all):
        L = new_all.shape[0]
        flat = new_all.reshape((L, Bp * Sp) + new_all.shape[3:])[:, src]
        return rc_ops.kv_scatter_inline(arena, pages, slots,
                                        flat.astype(arena.dtype),
                                        use_pallas=use_pallas,
                                        interpret=interpret)

    if has_writes and k_all is not None:
        k_arena = scatter(k_arena, k_all)
        v_arena = scatter(v_arena, v_all)
    tokens = _select_tokens(logits, temps, seed, use_pallas=use_pallas,
                            interpret=interpret)
    return tokens, logits, k_arena, v_arena, conv_arena, ssm_arena


def _fused_chunk_prefill_step(cfg, pcfg, params, toks, lens, offs, k_arena,
                              v_arena, bt, plens, pages, slots, src, seed,
                              temps, conv_arena=None, ssm_arena=None,
                              srows=None, *, has_writes: bool,
                              use_pallas: bool,
                              interpret: Optional[bool] = None,
                              axis: Optional[str] = None,
                              compressed: bool = False):
    """Chunk forward (prefix-KV attention over committed arena pages) +
    in-jit chunk-KV scatter + token selection: one prefill chunk batch
    as one compiled program over donated arenas.

    ``pages``/``slots``/``src`` are the chunk scatter plan, exactly as
    in :func:`_fused_prefill_step`; ``offs`` (B,) are the chunks'
    absolute position offsets (RoPE), ``bt``/``plens`` the prefix block
    tables and committed lengths.  ``has_writes=False`` (static: a batch
    of only no-write covered-sharer chunks) skips the scatter.  The
    scatter is traced *after* the forward's arena reads, so XLA orders
    the prefix gather before the in-place update on donated buffers.
    """
    logits, k_all, v_all, conv_arena, ssm_arena = _chunk_prefill_forward(
        cfg, pcfg, params, toks, lens, offs, k_arena, v_arena, bt, plens,
        use_pallas=use_pallas, interpret=interpret, axis=axis,
        compressed=compressed, conv_arena=conv_arena,
        ssm_arena=ssm_arena, srows=srows)
    Bp, Sp = toks.shape

    @jax.named_scope("kv_scatter")
    def scatter(arena, new_all):
        L = new_all.shape[0]
        flat = new_all.reshape((L, Bp * Sp) + new_all.shape[3:])[:, src]
        return rc_ops.kv_scatter_inline(arena, pages, slots,
                                        flat.astype(arena.dtype),
                                        use_pallas=use_pallas,
                                        interpret=interpret)

    if has_writes and k_all is not None:
        k_arena = scatter(k_arena, k_all)
        v_arena = scatter(v_arena, v_all)
    tokens = _select_tokens(logits, temps, seed, use_pallas=use_pallas,
                            interpret=interpret)
    return tokens, logits, k_arena, v_arena, conv_arena, ssm_arena


def _chunk_prefill_forward(cfg: ModelConfig, pcfg, params, toks, lens, offs,
                           k_arena, v_arena, bt, plens, *,
                           use_pallas: bool = False,
                           interpret: Optional[bool] = None,
                           axis: Optional[str] = None,
                           compressed: bool = False, conv_arena=None,
                           ssm_arena=None, srows=None):
    """Batched forward over one prefill *chunk* per row: ``lax.scan``
    over the stacked layer params AND the per-layer arena slices, with
    prefix-KV flash attention — each row's queries attend causally over
    the chunk and non-causally over the row's already-committed arena KV
    (gathered through its block table, masked at ``plens[b]`` so partial
    tail pages and table padding never leak).

    toks: (B, S) int32 chunk tokens; lens: (B,) valid chunk lengths
    (>= 1); offs: (B,) absolute position of each chunk's first token
    (drives RoPE); bt: (B, W) prefix block tables; plens: (B,) committed
    prefix lengths (0 = no prefix).  Returns (last-real-chunk-token
    logits (B, V), k_all, v_all (L, B, S, kvh, hd))."""
    hd = cfg.resolved_head_dim
    B, S = toks.shape
    ps = k_arena.shape[2]                # page size
    W = bt.shape[1]
    x = _embed_tokens(params["embed"], toks, cfg, axis)
    positions = offs[:, None] + jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_sincos(positions, hd, cfg.rope_theta)
    kinds = T.layer_groups(cfg)[0][1]
    has_attn = "attn" in kinds
    has_ssm = conv_arena is not None

    def body(x, xs):
        if has_ssm:
            p_layer, k_l, v_l, conv_l, ssm_l = xs
        else:
            p_layer, k_l, v_l = xs       # k_l: (pages, ps, kvh, hd)
            conv_l = ssm_l = None

        def attend(q, k, v):
            # gather this layer's committed prefix: (B, W*ps, kvh, hd)
            kp = k_l[bt].reshape(B, W * ps, k_l.shape[-2], k_l.shape[-1])
            vp = v_l[bt].reshape(B, W * ps, v_l.shape[-2], v_l.shape[-1])
            o = fa_ops.attention_inline(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True, sm_scale=hd ** -0.5,
                lengths=lens, k_prefix=kp.transpose(0, 2, 1, 3),
                v_prefix=vp.transpose(0, 2, 1, 3), prefix_lengths=plens,
                use_pallas=use_pallas, interpret=interpret)
            return o.transpose(0, 2, 1, 3)

        x, kv, conv_l, ssm_l = _run_kinds(
            cfg, pcfg, kinds, p_layer, x, sin, cos, attend, conv_l,
            ssm_l, srows, lens=lens, axis=axis)
        ys = ()
        if has_attn:
            ys += (kv,)
        if has_ssm:
            ys += ((conv_l, ssm_l),)
        return x, ys

    xs = (params["group0"], k_arena, v_arena)
    if has_ssm:
        xs += (conv_arena, ssm_arena)
    x, ys = jax.lax.scan(body, x, xs)
    k_all = v_all = conv_out = ssm_out = None
    if has_attn:
        k_all, v_all = ys[0]
    if has_ssm:
        conv_out, ssm_out = ys[-1]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # each row's last REAL chunk token (pad rows mirror row 0, lens >= 1)
    x_last = jnp.take_along_axis(
        x, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)
    logits = _logits_reduce(params["embed"], x_last, cfg, axis, compressed,
                            fp32=pcfg.logits_fp32)
    return logits[:, 0], k_all, v_all, conv_out, ssm_out


def _prefill_forward(cfg: ModelConfig, pcfg, params, toks, lens, *,
                     use_pallas: bool = False,
                     interpret: Optional[bool] = None,
                     axis: Optional[str] = None, compressed: bool = False,
                     conv_arena=None, ssm_arena=None, srows=None):
    """Batched prefill forward over a length-padded prompt batch:
    ``lax.scan`` over the stacked layer params (O(1) program size in
    depth) with causal + per-sequence-length masked flash attention —
    padded positions are never attended and their K/V never leave the
    step (the scatter plan only sources real tokens).  SSM sublayers
    run the length-masked paged scan from the rows' (freshly
    allocated, zero) arena state — pad positions carry state through
    unchanged, so the masked batch is bit-identical per row to a solo
    forward.

    toks: (B, S) int32 padded prompts; lens: (B,) valid lengths (>= 1).
    Returns (last-real-token logits (B, V), k_all, v_all
    (L, B, S, kvh, hd) | None, conv_arena, ssm_arena | None).
    """
    hd = cfg.resolved_head_dim
    B, S = toks.shape
    x = _embed_tokens(params["embed"], toks, cfg, axis)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_sincos(positions, hd, cfg.rope_theta)
    kinds = T.layer_groups(cfg)[0][1]
    has_attn = "attn" in kinds
    has_ssm = conv_arena is not None

    def attend(q, k, v):
        # (B, S, h, hd) <-> the kernel's (B, h, S, hd) layout
        o = fa_ops.attention_inline(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True, sm_scale=hd ** -0.5,
            lengths=lens, use_pallas=use_pallas, interpret=interpret)
        return o.transpose(0, 2, 1, 3)

    def body(x, xs):
        if has_ssm:
            p_layer, conv_l, ssm_l = xs
        else:
            p_layer = xs
            conv_l = ssm_l = None
        x, kv, conv_l, ssm_l = _run_kinds(
            cfg, pcfg, kinds, p_layer, x, sin, cos, attend, conv_l,
            ssm_l, srows, lens=lens, axis=axis)
        ys = ()
        if has_attn:
            ys += (kv,)
        if has_ssm:
            ys += ((conv_l, ssm_l),)
        return x, ys

    xs = ((params["group0"], conv_arena, ssm_arena) if has_ssm
          else params["group0"])
    x, ys = jax.lax.scan(body, x, xs)
    k_all = v_all = conv_out = ssm_out = None
    if has_attn:
        k_all, v_all = ys[0]
    if has_ssm:
        conv_out, ssm_out = ys[-1]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    # each row's last REAL token (pad rows mirror row 0, lens >= 1)
    x_last = jnp.take_along_axis(
        x, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)
    logits = _logits_reduce(params["embed"], x_last, cfg, axis, compressed,
                            fp32=pcfg.logits_fp32)
    return logits[:, 0], k_all, v_all, conv_out, ssm_out


def _select_tokens(logits: jax.Array, temps: jax.Array, seed: jax.Array, *,
                   use_pallas: bool, interpret: Optional[bool] = None,
                   rowmap: Optional[jax.Array] = None) -> jax.Array:
    """Per-request token choice: greedy rows take the argmax, sampled
    rows take a D-RaNGe inverse-CDF draw at their own temperature.  An
    all-greedy batch skips the TRNG + softmax entirely (lax.cond), and
    nothing here syncs to host — callers do one transfer per round.

    ``rowmap`` (the K-block loop's pad-row fold) remaps each row's
    uniform draw to ``u[rowmap[b]]``: real rows map to themselves, pad
    rows to row 0 — so a pad row samples the *same* token as the row it
    duplicates and the loop's next-round scatter writes identical values
    to identical slots (the single-round steps don't need this because
    their scatter values never depend on the sampled token)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled(_):
        u = dr_ops.pim_random_uniform(seed, logits.shape[0], 1,
                                      use_pallas=use_pallas,
                                      interpret=interpret)[:, 0]
        if rowmap is not None:
            u = u[rowmap]
        t = jnp.where(temps > 0.0, temps, 1.0)
        probs = jax.nn.softmax(logits.astype(jnp.float32) / t[:, None], axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        drawn = jnp.argmax(cum > u[:, None], axis=-1).astype(jnp.int32)
        return jnp.where(temps == 0.0, greedy, drawn)

    return jax.lax.cond(jnp.all(temps == 0.0), lambda _: greedy, sampled,
                        operand=None)


def _embed_tokens(p, tokens, cfg, axis=None):
    """Token embedding, host-local or vocab-parallel.

    Inside shard_map each shard holds vocab rows
    ``[axis_index * V_local, (axis_index + 1) * V_local)``: the shard
    owning a token contributes its exact (cast) table row, every other
    shard contributes exact zeros, and the ``psum`` is therefore
    bit-identical to the host-local ``jnp.take`` — adding 0.0 to a
    float is exact."""
    if axis is None:
        return embed(p, tokens, cfg)
    vloc = p["tok"].shape[0]
    start = jax.lax.axis_index(axis).astype(jnp.int32) * vloc
    local = tokens - start
    ok = (local >= 0) & (local < vloc)
    x = cast(jnp.take(p["tok"], jnp.clip(local, 0, vloc - 1), axis=0))
    x = jnp.where(ok[..., None], x, jnp.zeros_like(x))
    x = jax.lax.psum(x, axis)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


@jax.named_scope("logits")
def _logits_reduce(p, x, cfg, axis=None, compressed=False, fp32=True):
    """Output logits, host-local or vocab-parallel.

    Sharded: each shard computes its local (B, S, V_local) slice — the
    contraction dim (d_model) is NOT sharded, so each output element is
    the same multiply-accumulate the host-local einsum performs — then
    places it at ``axis_index * V_local`` in a zeros(V) buffer and
    reduces.  Plain ``psum`` sums exact zeros into each element
    (bit-identical to host-local math); ``compressed=True`` routes the
    reduce through :func:`repro.distributed.compression.psum_compressed`
    (int8 wire traffic, one quantization in / one out — logits agree to
    quantization tolerance, and the replicated argmax still picks one
    token for all shards)."""
    if axis is None:
        return logits_out(p, x, cfg, fp32=fp32)
    table = p.get("out", p["tok"])
    out = jnp.einsum("bsd,vd->bsv", x, cast(table))
    if fp32:
        out = out.astype(jnp.float32)
    vloc = table.shape[0]
    world = jax.lax.axis_size(axis)
    full = jnp.zeros(out.shape[:-1] + (vloc * world,), out.dtype)
    idx = (jnp.int32(0),) * (out.ndim - 1) + (
        jax.lax.axis_index(axis).astype(jnp.int32) * vloc,)
    full = jax.lax.dynamic_update_slice(full, out, idx)
    if compressed:
        return psum_compressed(full, axis)
    return jax.lax.psum(full, axis)


def _sublayer(cfg, kind, sp, x, sin, cos, attend, axis=None):
    """One decoder sublayer — the one source of truth shared by the
    fused decode scan, the eager decode loop, AND the fused prefill
    scan.  ``attend(q, k, v)`` supplies the attention dispatch over the
    full (b, s, h, hd) projections (decode callers attend one token
    against the arena, prefill callers run the length-masked flash
    kernel).  Returns (x, (k, v) | None) with k/v (b, s, kvh, hd).

    ``axis`` (inside shard_map): the weights are each shard's local
    slice — wq/wk/wv column-parallel over heads, wo and the MLP down
    projection row-parallel — so the only collectives a layer needs are
    the two residual-branch ``psum``s (Megatron-style TP).  The
    returned k/v are the shard's LOCAL kv-head slice: exactly what its
    arena shard stores."""
    h = rmsnorm(x, sp["norm"], cfg.norm_eps)
    if kind != "attn":
        with jax.named_scope("mlp"):
            y = mlp(sp["mlp"], h, cfg.activation)
            if axis is not None:
                y = jax.lax.psum(y, axis)
        return x + y, None
    with jax.named_scope("attention"):
        q = jnp.einsum("bsd,dhk->bshk", h, cast(sp["attn"]["wq"]))
        k = jnp.einsum("bsd,dhk->bshk", h, cast(sp["attn"]["wk"]))
        v = jnp.einsum("bsd,dhk->bshk", h, cast(sp["attn"]["wv"]))
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
        o = attend(q, k, v)
        out = jnp.einsum("bshk,hkd->bsd", o, cast(sp["attn"]["wo"]))
        if axis is not None:
            out = jax.lax.psum(out, axis)
    return x + out, (k, v)


def _run_kinds(cfg, pcfg, kinds, p_layer, x, sin, cos, attend, conv_l,
               ssm_l, srows, lens=None, axis=None, alive=None):
    """One scan step's sublayer sequence — the hybrid dispatch every
    fused forward AND the eager oracle follow in lockstep, so per-kind
    routing has exactly one implementation.

    ``attn``/``mlp`` route through :func:`_sublayer` unchanged.
    ``mamba`` sublayers gather their per-sequence recurrent state at
    ``srows`` from this step's state-arena slices ``conv_l``/``ssm_l``
    ((sublayers, slots, ...)), run decode (``lens is None``: one token)
    or the length-masked paged prefill scan, and scatter the fresh
    state back — pad rows duplicate row 0's inputs, so duplicate
    scatter destinations carry identical values and the ``.at[].set``
    stays deterministic.  ``alive`` (the K-block loop's row mask)
    freezes a dead row's state exactly as the masked KV scatter freezes
    its slot.  ``moe`` routes through the exact in-jit MoE (host-local
    engines always resolve to the dense fallback — per-token
    independent and jit-traceable, so fused stays bit-identical to
    eager); the router aux loss is a training artifact and is dropped.

    Returns (x, last-attn (k, v) | None, conv_l, ssm_l).
    """
    kv_out = None
    j = 0
    for i, kind in enumerate(kinds):
        sp = p_layer[f"{i}_{kind}"]
        if kind == "mamba":
            h = rmsnorm(x, sp["norm"], cfg.norm_eps)
            conv_j = conv_l[j][srows]
            ssm_j = ssm_l[j][srows]
            if lens is None:
                out, (nc, ns) = ssm_mod.ssm_layer(
                    cfg, pcfg, sp["ssm"], h, mode="decode",
                    cache=(conv_j, ssm_j))
            else:
                out, (nc, ns) = ssm_mod.ssm_layer_paged(
                    cfg, pcfg, sp["ssm"], h, lengths=lens,
                    conv_state=conv_j, ssm_state=ssm_j)
            x = x + out
            nc = nc.astype(conv_l.dtype)
            ns = ns.astype(ssm_l.dtype)
            if alive is not None:
                nc = jnp.where(alive[:, None, None], nc, conv_j)
                ns = jnp.where(alive[:, None, None, None], ns, ssm_j)
            conv_l = conv_l.at[j, srows].set(nc)
            ssm_l = ssm_l.at[j, srows].set(ns)
            j += 1
        elif kind == "moe":
            h = rmsnorm(x, sp["norm"], cfg.norm_eps)
            with jax.named_scope("mlp"):
                out, _aux = moe_mod.moe_layer(cfg, pcfg, sp["moe"], h)
            x = x + out
        else:
            x, kv = _sublayer(cfg, kind, sp, x, sin, cos, attend,
                              axis=axis)
            if kv is not None:
                kv_out = kv
    return x, kv_out, conv_l, ssm_l


def _paged_decode_forward(cfg: ModelConfig, pcfg, params, tokens, k_arena,
                          v_arena, block_tables, lengths, *,
                          use_pallas: bool = False,
                          interpret: Optional[bool] = None,
                          axis: Optional[str] = None,
                          compressed: bool = False, conv_arena=None,
                          ssm_arena=None, srows=None, alive=None):
    """Decoder forward for one token: ``lax.scan`` over the stacked
    layer params and the layer index — O(1) program size in depth.  The
    arenas are not scanned: each step's paged attention reads its
    layer's pages in place in the stacked arena, and the current token's
    K/V merges inside the paged kernel.
    With SSM sublayers (``conv_arena`` set) the scan's xs extend with
    the per-step state-arena slices and the updated arenas ride out as
    stacked ys — still one scan, zero extra launches.

    With ``axis`` (inside shard_map) the params/arenas are each shard's
    local head slice and the activations are tensor-parallel (see
    :func:`_sublayer` / :func:`_logits_reduce`).

    Returns (logits (b,1,V), k_new, v_new (L, b, 1, kvh, hd) | None,
    conv_arena, ssm_arena | None).
    """
    hd = cfg.resolved_head_dim
    x = _embed_tokens(params["embed"], tokens, cfg, axis)
    positions = lengths[:, None].astype(jnp.int32)  # token pos == length
    sin, cos = rope_sincos(positions, hd, cfg.rope_theta)
    kinds = T.layer_groups(cfg)[0][1]
    has_attn = "attn" in kinds
    has_ssm = conv_arena is not None

    def body(x, xs):
        if has_ssm:
            p_layer, li, conv_l, ssm_l = xs
        else:
            p_layer, li = xs
            conv_l = ssm_l = None

        def attend(q, k, v):
            # one token against layer li's pages, read in place in the
            # stacked arena, with the fresh K/V (not yet written) merged
            # in-kernel
            o = pa_ops.paged_attention_inline(
                q[:, 0], k_arena, v_arena, li, block_tables, lengths,
                sm_scale=hd ** -0.5, use_pallas=use_pallas,
                interpret=interpret, k_self=k[:, 0], v_self=v[:, 0])
            return o[:, None]

        x, kv, conv_l, ssm_l = _run_kinds(
            cfg, pcfg, kinds, p_layer, x, sin, cos, attend, conv_l,
            ssm_l, srows, axis=axis, alive=alive)
        ys = ()
        if has_attn:
            ys += ((kv[0][:, 0], kv[1][:, 0]),)
        if has_ssm:
            ys += ((conv_l, ssm_l),)
        return x, ys

    # the arenas stay loop-invariant operands: xs carries the layer index
    xs = (params["group0"], jnp.arange(k_arena.shape[0], dtype=jnp.int32))
    if has_ssm:
        xs += (conv_arena, ssm_arena)
    x, ys = jax.lax.scan(body, x, xs)
    k_news = v_news = conv_out = ssm_out = None
    if has_attn:
        k_news, v_news = ys[0]
    if has_ssm:
        conv_out, ssm_out = ys[-1]
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits_reduce(params["embed"], x, cfg, axis, compressed)
    if has_attn:
        k_news, v_news = k_news[:, :, None], v_news[:, :, None]
    return logits, k_news, v_news, conv_out, ssm_out


def _eager_decode_forward(cfg: ModelConfig, pcfg, params, tokens, k_arena,
                          v_arena, block_tables, lengths, *,
                          use_pallas: bool = False,
                          interpret: Optional[bool] = None,
                          conv_arena=None, ssm_arena=None, srows=None):
    """Pre-fusion baseline: Python loop over layers, one jitted
    paged-attention dispatch per layer.  Shares ``_sublayer`` and the
    hybrid :func:`_run_kinds` dispatch with the fused path (the
    self-token merge still happens in-kernel — the old full-history
    re-reading merge pass is gone).  With SSM sublayers, returns the
    batch's fresh state VALUES (G, M, b, ...) — the engine writes them
    back through the op queue's ``ssm_state_write`` kind, the eager
    analogue of the fused path's in-jit scatter."""
    hd = cfg.resolved_head_dim
    x = embed(params["embed"], tokens, cfg)
    positions = lengths[:, None].astype(jnp.int32)  # token pos == length
    sin, cos = rope_sincos(positions, hd, cfg.rope_theta)
    gparams = params["group0"]
    L, kinds = T.layer_groups(cfg)[0]
    has_ssm = conv_arena is not None

    def layer_attend(li):
        def attend(q, k, v):
            o = pa_ops.paged_attention(
                q[:, 0], k_arena, v_arena, li, block_tables, lengths,
                sm_scale=hd ** -0.5, use_pallas=use_pallas,
                interpret=interpret, k_self=k[:, 0], v_self=v[:, 0])
            return o[:, None]
        return attend

    k_news, v_news = [], []
    conv_news, ssm_news = [], []
    for li in range(L):
        p_layer = jax.tree.map(lambda a: a[li], gparams)
        attend = layer_attend(li)
        conv_l = conv_arena[li] if has_ssm else None
        ssm_l = ssm_arena[li] if has_ssm else None
        x, kv, conv_l, ssm_l = _run_kinds(
            cfg, pcfg, kinds, p_layer, x, sin, cos, attend, conv_l,
            ssm_l, srows)
        if kv is not None:
            k_news.append(kv[0][:, 0][None])   # (1, b, kvh, hd)
            v_news.append(kv[1][:, 0][None])
        if has_ssm:
            # eager rids are unique (no pad rows), so gathering the
            # just-set rows back yields exactly the fresh values
            conv_news.append(conv_l[:, srows][None])   # (1, M, b, ...)
            ssm_news.append(ssm_l[:, srows][None])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_out(params["embed"], x, cfg)
    k_new = v_new = conv_new = ssm_new = None
    if k_news:
        k_new = jnp.concatenate(k_news, axis=0)[:, :, None]  # (L,b,1,kvh,hd)
        v_new = jnp.concatenate(v_news, axis=0)[:, :, None]
    if has_ssm:
        conv_new = jnp.concatenate(conv_news, axis=0)   # (G, M, b, ...)
        ssm_new = jnp.concatenate(ssm_news, axis=0)
    return logits, k_new, v_new, conv_new, ssm_new
