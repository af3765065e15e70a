"""Serving driver: batched requests through the paged PiM engine.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
      --layers 20 --num-pages 2048 --requests 8 --prompt-len 24

serves the published widths with the depth cut to 20 layers (one v5e
chip); ``--reduced`` serves the toy CPU preset instead.

This is the closed-loop batch driver; the open-system async front door
(streaming, Poisson arrivals, SLOs) lives in
``repro.launch.serve_async``.  :func:`build_engine` is the one way an
entry point (this driver, ``chip_smoke.py``) builds its engine.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, ModelConfig, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.engine import PagedEngine, Request


def serving_config(arch: str, *, layers: Optional[int] = None,
                   reduced_widths: bool = False) -> ModelConfig:
    """The model an engine serves: ``arch``'s published config with only
    its depth cut to ``layers``, or with ``reduced_widths`` the toy
    preset the CPU runs use."""
    cfg = ARCHS[arch]
    if reduced_widths:
        cfg = reduced(cfg)
    if layers is not None:
        if not 0 < layers <= cfg.num_layers:
            raise ValueError(f"{arch} has {cfg.num_layers} layers, "
                             f"cannot serve {layers}")
        cfg = dataclasses.replace(cfg, num_layers=layers)
    return cfg


def build_engine(cfg: ModelConfig, *, params=None, seed: int = 0,
                 num_pages: int = 256, page_size: int = 16,
                 **engine_kw) -> PagedEngine:
    """A :class:`PagedEngine` over seeded random bf16 weights — each leaf
    drawn at bf16, never through a float32 copy — or over ``params``
    taken from another engine (two engines then share one copy).
    ``engine_kw`` passes through (``use_pallas``, ``prefix_cache``,
    ``max_prefill_chunk``, ``mesh``, ...)."""
    if params is None:
        params = init_params(T.model_defs(cfg), jax.random.PRNGKey(seed),
                             jnp.bfloat16)
    return PagedEngine(cfg, params, page_size=page_size,
                       num_pages=num_pages, seed=seed, **engine_kw)


def byte_budget(engine: PagedEngine) -> dict:
    """What the engine holds on its devices: the depth cut, the weights
    and the KV arenas (plus the SSM state arenas where the model has
    them), in bytes summed over devices."""
    cfg, cache = engine.cfg, engine.cache
    kv = cache.k_arena.nbytes + cache.v_arena.nbytes
    slots = cache.k_arena.shape[1] * cache.page_size
    out = {"arch": cfg.name, "layers": cfg.num_layers,
           "layers_published": ARCHS[cfg.name].num_layers,
           "weight_bytes": sum(x.nbytes for x in
                               jax.tree.leaves(engine.params)),
           "kv_pages": cache.k_arena.shape[1], "page_size": cache.page_size,
           "kv_arena_bytes": kv, "kv_bytes_per_token": kv // slots}
    if cache.state is not None:
        out["state_arena_bytes"] = cache.state.conv.nbytes \
            + cache.state.ssm.nbytes
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--share-prefix", action="store_true",
                    help="second half of requests reuse the first prompt "
                         "(minus a fresh 4-token tail); the radix prefix "
                         "cache dedupes the shared pages automatically")
    ap.add_argument("--share-pairwise", action="store_true",
                    help="DEPRECATED: same workload through the legacy "
                         "pairwise share_with/shared_len arithmetic the "
                         "prefix cache replaced — kept as the sharing "
                         "parity oracle")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths stay "
                         "as published)")
    ap.add_argument("--reduced", action="store_true",
                    help="toy widths (d_model 128, vocab 512) for CPU runs")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = serving_config(args.arch, layers=args.layers,
                         reduced_widths=args.reduced)
    engine = build_engine(cfg, num_pages=args.num_pages,
                          page_size=args.page_size,
                          prefix_cache=args.share_prefix)
    print(json.dumps({"byte_budget": byte_budget(engine)}))

    rng = np.random.default_rng(0)
    base_prompt = rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32)
    results = {}
    t0 = time.time()
    if args.share_prefix:
        # radix path: commit the base prompt once, then submit the
        # sharers with no sharing arguments at all — create(...,
        # tokens=) longest-prefix-matches their full pages against the
        # committed tree
        engine.submit(Request(0, base_prompt, max_new_tokens=args.max_new))
        results.update(engine.run())
    for i in range(1 if args.share_prefix else 0, args.requests):
        if (args.share_prefix or args.share_pairwise) \
                and i >= args.requests // 2:
            p = base_prompt.copy()
            p[-4:] = rng.integers(0, cfg.vocab_size, 4)
            if args.share_pairwise:
                engine.submit(Request(i, p, max_new_tokens=args.max_new,
                                      share_with=0,
                                      shared_len=(args.prompt_len - 4)
                                      // args.page_size * args.page_size))
            else:
                engine.submit(Request(i, p, max_new_tokens=args.max_new))
        else:
            engine.submit(Request(i, base_prompt if i == 0 else
                                  rng.integers(0, cfg.vocab_size,
                                               args.prompt_len).astype(np.int32),
                                  max_new_tokens=args.max_new))
    results.update(engine.run())
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    print(json.dumps({
        "requests": len(results), "tokens": toks,
        "tok_per_s": round(toks / dt, 1),
        "engine_stats": engine.stats,
        "cache_stats": engine.cache.stats,
        "ops_saved_by_sharing": engine.cache.queue.saved_by_kind,
        "pages_in_use_at_end": engine.cache.pages_in_use,
    }, indent=1))
    for rid in sorted(results)[:4]:
        print(rid, results[rid][:10])


if __name__ == "__main__":
    main()
