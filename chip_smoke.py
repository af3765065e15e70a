#!/usr/bin/env python3
"""Chip smoke: the serving main path, end to end, on TPU v5e.

  python chip_smoke.py               # one chip: granite-3-8b
  python chip_smoke.py --four-chips  # four chips: minitron-8b on a mesh

One chip.  granite-3-8b at its published widths (d_model 4096, 32/8
heads of 128, d_ff 12,800, vocab 49,155) with only the depth cut, to 20
of 40 layers: about 8.4 GB of seeded random bf16 weights and a KV arena
of 2,048 pages of 16 tokens (2.7 GB at 80 KB a token) on a 16 GB chip.
Requests go through ``PagedEngine.submit``/``run``, built by
``repro.launch.serve.build_engine`` as the serving driver builds it, in
two passes:

* fused prefill with the radix prefix cache: eight prompts of 80-1,000
  tokens, then eight more, two of which share a prefix with the first
  eight;
* chunked prefill (``max_prefill_chunk=512``): a short and a long
  prompt, so the chunk, mixed and decode steps all run.

Both passes run twice: on the jnp references (``use_pallas=False``, the
engine's default) and on the Pallas kernels (``use_pallas=True``).
Every request must get its tokens, every prefill's logits must be
finite, the arena must come back to the pages the prefix cache keeps,
and each request's prefill logits must agree between the two runs.

Four chips (``--four-chips``, and nothing else then).  minitron-8b at
its published widths (32/8 heads of 128, d_ff 16,384, vocab 256,000),
cut to 8 of 32 layers so that one chip can also hold it (about 8.1 GB):
served tensor-parallel through ``PagedEngine(mesh=make_local_mesh(
model=4))`` and host-local on ``jax.devices()[0]``, with the prefill
logits compared.  Each chip must hold a quarter of the weights.  The
full 32-layer mesh-4 decode step is then compiled (not run) for the
four chips, and its ``memory_analysis()`` printed per chip.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  Any failed check exits non-zero
before it.  With no TPU, or outside the repository, the script exits
non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# one bf16 unit in the last place, relative: 2^-8 (8 significand bits)
BF16_ULP = 2.0 ** -8


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Seconds the backend spent compiling, from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def logit_tolerance(ref, layers: int, ulps_per_layer: int) -> float:
    """Largest |logit difference| two bf16 runs of one model may show when
    they differ only in the order of some reductions.

    Each reordered reduction can move its bf16 result by about one unit
    in the last place, and each layer adds its move to the residual
    stream; with random weights the layers neither grow nor shrink such
    a perturbation much, so the moves add up at most linearly in depth.
    A masking or indexing fault moves logits by their own scale, far
    outside this bound."""
    import numpy as np
    return float(layers * ulps_per_layer * BF16_ULP
                 * np.max(np.abs(ref)))


def compare_logits(ref: dict, got: dict, layers: int, ulps: int) -> dict:
    import numpy as np
    check(ref.keys() == got.keys(), "the two runs served different requests")
    worst = {"max_abs_diff": 0.0, "tolerance": None, "rel_rms": 0.0}
    for rid in sorted(ref):
        a = np.asarray(ref[rid], np.float32)
        b = np.asarray(got[rid], np.float32)
        tol = logit_tolerance(a, layers, ulps)
        diff = float(np.max(np.abs(a - b)))
        rel = float(np.sqrt(np.mean((a - b) ** 2) / np.mean(a ** 2)))
        check(diff <= tol, f"request {rid}: prefill logits differ by {diff} "
                           f"> tolerance {tol}")
        if diff >= worst["max_abs_diff"]:
            worst.update(max_abs_diff=diff, tolerance=tol, request=rid)
        worst["rel_rms"] = max(worst["rel_rms"], rel)
    return worst


def serve(engine, batches, *, label: str, clock: CompileClock) -> dict:
    """Submit each batch of Requests and run the engine to completion;
    check tokens, logits and pages.  Returns {req_id: prefill logits}."""
    import numpy as np
    t0, c0 = time.perf_counter(), clock.seconds
    logits, tokens = {}, 0
    for batch in batches:
        for r in batch:
            engine.submit(r)
        done = engine.run()
        for r in batch:
            check(r.req_id in done and len(done[r.req_id]) == r.max_new_tokens,
                  f"{label}: request {r.req_id} got "
                  f"{len(done.get(r.req_id, []))} of {r.max_new_tokens} tokens")
            lg = np.asarray(r.logits, np.float32)
            check(lg.shape == (engine.cfg.vocab_size,),
                  f"{label}: request {r.req_id} logits shape {lg.shape}")
            check(bool(np.isfinite(lg).all()),
                  f"{label}: request {r.req_id} has non-finite logits")
            logits[r.req_id] = lg
            tokens += len(done[r.req_id])
    cache = engine.cache
    kept = len(cache.prefix.pages_indexed()) if cache.prefix else 0
    check(cache.pages_in_use == kept,
          f"{label}: {cache.pages_in_use} pages in use after the run, the "
          f"prefix cache keeps {kept}")
    emit("serve", run=label, requests=len(logits), tokens=tokens,
         wall_s=time.perf_counter() - t0,
         compile_s=clock.seconds - c0,
         prefill_dispatches=engine.stats["fused_prefill_dispatches"],
         mixed_dispatches=engine.stats["mixed_dispatches"],
         decode_rounds=engine.stats["decode_rounds"],
         prefix_hits=engine.stats["prefix_hits"],
         pages_in_use=cache.pages_in_use, prefix_pages_kept=kept)
    return logits


def granite_requests(vocab: int, seed: int):
    """The one-chip traffic, identical for both runs: (fused pass
    batches, chunked pass batch).  Lengths fall in two prefill buckets
    (128 and 1024) so each pass compiles few programs."""
    import numpy as np
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    lens = [80, 96, 112, 128, 600, 777, 900, 1000]
    first = [prompt(n) for n in lens]
    second = [prompt(n) for n in lens]
    # two sharers: a 512-token (32-page) prefix of earlier prompts, then
    # a fresh tail; the prefix cache attaches the committed pages
    second[4] = np.concatenate([first[6][:512], prompt(lens[4] - 512)])
    second[5] = np.concatenate([first[7][:512], prompt(lens[5] - 512)])
    chunked = [prompt(100), prompt(1000)]

    def reqs(prompts, base):
        # one sampled request per batch, so the D-RaNGe draw runs too
        return [Request(base + i, p, max_new_tokens=16,
                        temperature=0.8 if i == 0 else 0.0, keep_logits=True)
                for i, p in enumerate(prompts)]

    return ([reqs(first, 0), reqs(second, 100)], [reqs(chunked, 200)])


def one_chip(args, dev, clock: CompileClock) -> None:
    import jax
    from repro.launch.serve import build_engine, byte_budget, serving_config

    cfg = serving_config("granite-3-8b", layers=20)
    layers = cfg.num_layers
    emit("config", arch=cfg.name, layers=layers,
         layers_published=40, d_model=cfg.d_model, heads=cfg.num_heads,
         kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
         d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         cut="depth 40 -> 20 layers; every width as published")
    params = None
    runs = {}
    for use_pallas in (False, True):
        label = "pallas" if use_pallas else "reference"
        fused_batches, chunk_batches = granite_requests(cfg.vocab_size,
                                                        args.seed)
        t0 = time.perf_counter()
        eng = build_engine(cfg, params=params, seed=args.seed,
                           num_pages=2048, use_pallas=use_pallas,
                           prefix_cache=True)
        jax.block_until_ready((eng.params, eng.cache.k_arena))
        emit("build", run=label, with_weights=params is None,
             seconds=time.perf_counter() - t0)
        if params is None:
            emit("budget", **byte_budget(eng))
        params = eng.params
        logits = serve(eng, fused_batches, label=f"{label}/fused",
                       clock=clock)
        del eng
        gc.collect()      # the engine's step closures hold it in a cycle
        eng = build_engine(cfg, params=params, seed=args.seed,
                           num_pages=2048, use_pallas=use_pallas,
                           max_prefill_chunk=512)
        logits.update(serve(eng, chunk_batches, label=f"{label}/chunked",
                            clock=clock))
        check(eng.stats["mixed_dispatches"] > 0,
              f"{label}/chunked: no mixed round ran")
        del eng
        gc.collect()
        runs[label] = logits
        emit("memory", run=label,
             peak_bytes_in_use=dev.memory_stats()["peak_bytes_in_use"])
    # one reordered reduction a layer: prefill attention, summed in flash
    # tiles by the kernel and in one pass by the reference (the KV
    # scatter and page ops move bits and change none)
    diff = compare_logits(runs["reference"], runs["pallas"], layers, 1)
    emit("logits", compared="use_pallas=False vs True", **diff)
    emit("compile", compile_s=clock.seconds, programs=clock.count)


def four_chips(args, clock: CompileClock) -> None:
    import jax
    import numpy as np
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_engine, byte_budget, serving_config
    from repro.serving.engine import Request

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    cfg = serving_config("minitron-8b", layers=8)
    layers = cfg.num_layers
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (100, 120, 700, 900)]

    def batch():
        return [[Request(i, p, max_new_tokens=16, keep_logits=True)
                 for i, p in enumerate(prompts)]]

    emit("config", arch=cfg.name, layers=layers, layers_published=32,
         d_model=cfg.d_model, heads=cfg.num_heads,
         kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff, vocab=cfg.vocab_size,
         cut="depth 32 -> 8 layers; every width as published")
    t0 = time.perf_counter()
    eng = build_engine(cfg, seed=args.seed, num_pages=512)
    jax.block_until_ready((eng.params, eng.cache.k_arena))
    emit("build", run="host-local", with_weights=True,
         seconds=time.perf_counter() - t0)
    emit("budget", **byte_budget(eng))
    local = serve(eng, batch(), label="host-local", clock=clock)
    params = eng.params
    del eng
    gc.collect()

    mesh = make_local_mesh(model=4)
    t0 = time.perf_counter()
    eng = build_engine(cfg, params=params, seed=args.seed, num_pages=512,
                       mesh=mesh)
    jax.block_until_ready((eng.params, eng.cache.k_arena))
    emit("build", run="mesh4", with_weights=False,
         seconds=time.perf_counter() - t0)
    del params     # only the mesh engine's shards stay on the chips
    gc.collect()
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(eng.params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    shares = {str(d.id): held[d] / total for d in held}
    emit("placement", weight_bytes=total,
         weight_bytes_per_device={str(d.id): held[d] for d in held},
         bytes_in_use={str(d.id): d.memory_stats()["bytes_in_use"]
                       for d in held})
    for d, share in shares.items():
        check(abs(share - 0.25) < 0.01,
              f"device {d} holds {share:.4f} of the weights, not a quarter")
    sharded = serve(eng, batch(), label="mesh4", clock=clock)
    del eng
    gc.collect()
    # two row-parallel psums a layer (attention-out, MLP-down), each
    # summing four bf16-rounded partials: about two bf16 units of the
    # result apiece
    diff = compare_logits(local, sharded, layers, 4)
    emit("logits", compared="host-local vs mesh 4", **diff)
    emit("memory", peak_bytes_in_use={
        str(d.id): d.memory_stats()["peak_bytes_in_use"] for d in devs[:4]})
    compile_full_depth(devs[:4])
    emit("compile", compile_s=clock.seconds, programs=clock.count)


def compile_full_depth(devices) -> None:
    """Compile (never run) the full 32-layer minitron-8b decode step,
    tensor-parallel over four chips, from shapes alone; print what each
    chip would hold."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.configs import ParallelConfig
    from repro.distributed.sharding import sharding_env
    from repro.launch.serve import serving_config
    from repro.models import transformer as T
    from repro.models.params import abstract_params, param_specs
    from repro.serving import engine as E

    cfg = serving_config("minitron-8b")
    mesh = Mesh(np.asarray(devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    defs = T.model_defs(cfg)
    with sharding_env(mesh, fsdp=False):
        specs = param_specs(defs)
    arena_spec = P(None, None, None, "model", None)
    batch, pages, width = 16, 2048, 64
    fn = functools.partial(E._fused_decode_step, cfg,
                           ParallelConfig(attention_impl="naive",
                                          remat="none"),
                           use_pallas=True, axis="model")
    step = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(specs,) + (P(),) + (arena_spec,) * 2 + (P(),) * 6,
        out_specs=(P(), arena_spec, arena_spec, P(), P()), check_vma=False)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(lambda a, s: sds(a.shape, a.dtype, s),
                          abstract_params(defs, jnp.bfloat16), specs)
    arena = sds((cfg.num_layers, pages, 16, cfg.num_kv_heads,
                 cfg.resolved_head_dim), jnp.bfloat16, arena_spec)
    i32 = jnp.int32
    args = (params, sds((batch, 1), i32), arena, arena,
            sds((batch, width), i32), sds((batch,), i32),
            sds((batch,), i32), sds((batch,), i32), sds((2,), jnp.uint32),
            sds((batch,), jnp.float32))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(2, 3)).lower(*args).compile()
    mem = compiled.memory_analysis()
    emit("full_depth_compile", arch=cfg.name, layers=cfg.num_layers,
         mesh=4, compile_s=time.perf_counter() - t0,
         kernels="tpu_custom_call" in compiled.as_text(),
         per_device={"argument_bytes": mem.argument_size_in_bytes,
                     "output_bytes": mem.output_size_in_bytes,
                     "alias_bytes": mem.alias_size_in_bytes,
                     "temp_bytes": mem.temp_size_in_bytes})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: run from a checkout of the repository "
              "(src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit("device", compile_cache=cache_dir, **device)
    clock = CompileClock()
    try:
        if args.four_chips:
            four_chips(args, clock)
        else:
            one_chip(args, dev, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
