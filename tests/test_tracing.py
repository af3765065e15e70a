"""The serving round's own instrumentation: host profiler spans
(``engine.round`` and its phases, ``server.*``), the fused steps' jit
names, and the pad counters in ``engine.stats``.

The spans are read back from a real ``jax.profiler`` trace taken on the
CPU; the jit names from each step's lowering; the counters from a hand
built round whose rows, lengths and table width are known."""

import asyncio
import glob

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import transformer as T
from repro.models.params import init_params
from repro.serving.engine import PagedEngine, Request
from repro.serving.server import AsyncServer

ENGINE_SPANS = {"engine.round", "engine.admit", "engine.plan",
                "engine.flush", "engine.sync", "engine.commit",
                "engine.dispatch.decode", "engine.dispatch.chunk",
                "engine.dispatch.prefill", "engine.dispatch.block",
                "engine.dispatch.mixed"}


@pytest.fixture(scope="module")
def model():
    cfg = reduced(ARCHS["granite-3-8b"], num_layers=1)
    params = init_params(T.model_defs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _engine(model, **kw):
    cfg, params = model
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 64)
    return PagedEngine(cfg, params, **kw)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, n).astype(np.int32)


def _steps(eng) -> int:
    n = 0
    while eng.has_work:
        eng.step()
        n += 1
    return n


def _host_spans(log_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in ln.events
                        if e.name.startswith(("engine.", "server."))]
    return out


def test_every_span_inside_its_round(model, tmp_path):
    """Under the profiler a chunked engine (chunk, mixed, K-block and
    single decode steps), a monolithic one (prefill step) and the async
    server emit every span name; each engine span lies inside an
    ``engine.round``; ``step()`` opens exactly one round and ``run()``
    one per iteration."""
    cfg, _ = model
    chunked = _engine(model, max_prefill_chunk=8, decode_block_rounds=2)
    mono = _engine(model)
    served = _engine(model, max_prefill_chunk=8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        # run(), four iterations: a lone chunk of the 12-token prompt,
        # the chunk finishing it fused with its first decode (mixed),
        # a K=2 block, and a last single decode for the 5th token
        chunked.submit(Request(0, _prompt(cfg, 12, 0), max_new_tokens=5,
                               temperature=0.0))
        chunked.run()
        assert chunked.stats["multi_round_blocks"] == 1
        mono.submit(Request(0, _prompt(cfg, 6, 1), max_new_tokens=3,
                            temperature=0.0))
        steps = _steps(mono)

        async def serve():
            async with AsyncServer(served) as srv:
                st = await srv.submit(_prompt(cfg, 10, 2), max_new_tokens=3,
                                      temperature=0.0)
                toks = [t async for t in st]
            return toks, srv.stats["rounds"]

        toks, served_rounds = asyncio.run(asyncio.wait_for(serve(), 300))
        assert len(toks) == 3
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    names = {n for n, _, _ in spans}
    assert ENGINE_SPANS | {"server.admit", "server.publish"} <= names
    rounds = [(s, e) for n, s, e in spans if n == "engine.round"]
    assert len(rounds) == 4 + steps + served_rounds
    for n, s, e in spans:
        if n.startswith("engine.") and n != "engine.round":
            assert any(a <= s and e <= b for a, b in rounds), n


@pytest.mark.parametrize("mixed", [False, True])
def test_pad_counters_on_a_hand_built_round(model, mixed):
    """Three prompts of 9, 12 and 15 tokens in one chunk batch (bucket
    16, rows padded to 4), then their first decode over pages of 4
    tokens: 12 needs a fourth page for its next token, so the table is 4
    pages wide for all four rows (the pad row repeats row 0)."""
    cfg, _ = model
    eng = _engine(model, max_prefill_chunk=64, mixed_rounds=mixed)
    for i, n in enumerate((9, 12, 15)):
        eng.submit(Request(i, _prompt(cfg, n, i), max_new_tokens=3,
                           temperature=0.0))
    eng.step()
    s = eng.stats
    assert (s["chunk_tokens"], s["chunk_token_slots"]) == (36, 4 * 16)
    assert (s["decode_kv_tokens"], s["decode_kv_slots"]) == (36, 4 * 4 * 4)
    eng.step()        # lengths 10, 13, 16: 16's next token opens page 5
    assert (s["decode_kv_tokens"], s["decode_kv_slots"]) == (
        36 + 39, 4 * 4 * 4 + 4 * 8 * 4)
    assert (s["chunk_tokens"], s["chunk_token_slots"]) == (36, 64)


def test_five_steps_lower_to_five_module_names(model):
    """Each fused step is its own device program in a profile:
    ``jit_decode_step``, ``jit_chunk_step``, ``jit_prefill_step``,
    ``jit_block_step`` and ``jit_mixed_step``."""
    eng = _engine(model, max_prefill_chunk=8, decode_block_rounds=2)
    p, k, v = eng.params, eng.cache.k_arena, eng.cache.v_arena
    B, S, W, K = 2, 8, 4, 2
    i32 = lambda *s: np.zeros(s, np.int32)          # noqa: E731
    f32 = np.zeros((B,), np.float32)
    seed = eng.rng_seed
    lowered = {
        "decode": eng._step.lower(p, i32(B, 1), k, v, i32(B, W), i32(B),
                                  i32(B), i32(B), seed, f32, None, None,
                                  None),
        "chunk": eng._chunk_step.lower(
            p, i32(B, S), i32(B), i32(B), k, v, i32(B, W), i32(B),
            i32(B * S), i32(B * S), i32(B * S), seed, f32, None, None, None,
            has_writes=True),
        "prefill": eng._prefill_step.lower(
            p, i32(B, S), i32(B), k, v, i32(B * S), i32(B * S), i32(B * S),
            seed, f32, None, None, None, has_writes=True),
        "block": eng._block_step.lower(
            p, i32(B), i32(B), k, v, i32(B, W), i32(B), i32(B, K),
            i32(B, K), i32(B), seed, f32, i32(B), None, None, None),
        "mixed": eng._mixed_step.lower(
            p, i32(B, S), i32(B), i32(B), k, v, i32(B, W), i32(B),
            i32(B * S), i32(B * S), i32(B * S), seed, f32, i32(B),
            i32(B, W), i32(B), i32(B), i32(B), seed, f32, i32(B), None,
            None, None, None, has_writes=True),
    }
    names = {kind: low.as_text().split("module @", 1)[1].split(" ", 1)[0]
             for kind, low in lowered.items()}
    assert names == {kind: f"jit_{kind}_step" for kind in lowered}
