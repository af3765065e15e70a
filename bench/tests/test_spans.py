"""The program's own spans, scopes and counters, and the metric readers
that read them, on hand-made traces and contexts."""

from types import SimpleNamespace as NS

import pytest

from bench import run as R
from bench.core import spans, trace

MS = 1e6
ENGINE = "jit(decode_step)/while/body/closed_call"


def _tr(program=True):
    """One chip, window 0..100 ms.  Two engine rounds, 10-50 and 60-90
    ms, each with its phases; a server span 50-55 between them.  The
    decode step runs 15-35 ms (a loop holding an attention, an MLP and a
    logits op), the chunk step 71-80 ms.  Without ``program`` the trace
    is what a program without spans or scopes writes."""
    ops = [("while.1", 15 * MS, 35 * MS, "jit(decode_step)/while"),
           ("fusion.1", 15 * MS, 25 * MS, f"{ENGINE}/attention/dot_general"),
           ("fusion.2", 25 * MS, 30 * MS, f"{ENGINE}/mlp/dot_general"),
           ("copy.3", 30 * MS, 35 * MS, "jit(decode_step)/logits/dot"),
           ("fusion.9", 71 * MS, 80 * MS,
            "jit(chunk_step)/while/body/attention/gather")]
    host = [("bench.mark", 0, 0.01 * MS), ("bench.mark", 100 * MS,
                                            100.01 * MS),
            ("engine.round", 10 * MS, 50 * MS),
            ("engine.plan", 10 * MS, 14 * MS),
            ("engine.dispatch.decode", 14 * MS, 16 * MS),
            ("engine.sync", 16 * MS, 40 * MS),
            ("engine.commit", 40 * MS, 50 * MS),
            ("server.publish", 50 * MS, 55 * MS),
            ("engine.round", 60 * MS, 90 * MS),
            ("engine.plan", 60 * MS, 70 * MS),
            ("engine.dispatch.chunk", 70 * MS, 72 * MS),
            ("engine.sync", 72 * MS, 85 * MS),
            ("engine.commit", 85 * MS, 90 * MS)]
    mods = [("jit_decode_step(5)", 15 * MS, 35 * MS),
            ("jit_chunk_step(7)", 71 * MS, 80 * MS)]
    if not program:
        ops = [(n, s, e, "") for n, s, e, _ in ops]
        host = [h for h in host if not h[0].startswith(("engine.",
                                                         "server."))]
        mods = [("jit_step(5)", 15 * MS, 35 * MS),
                ("jit_step(7)", 71 * MS, 80 * MS)]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host}


def _plain(tr):
    """The same trace as ``bench.core.trace.load`` keeps it."""
    dev = {k: {"ops": [o[:3] for o in d["ops"]], "modules": d["modules"]}
           for k, d in tr["devices"].items()}
    host = [h for h in tr["host"] if h[0] in trace.HOST_SPANS]
    return {"devices": dev, "host": host}


def _ctx(tr, **counters):
    c1 = {f"engine.{k}": v for k, v in counters.items()}
    return NS(trace=trace.summarise(_plain(tr)), program=spans.summarise(tr),
              win=NS(counters0={}, counters1=c1))


def test_program_summary_on_a_hand_made_trace():
    p = spans.summarise(_tr())
    assert p.window_s == pytest.approx(0.1)
    # busy 15-35 and 71-80: 71 ms idle, 41 of it inside the two rounds
    assert p.idle_in_round_s == pytest.approx(0.041)
    assert p.idle_outside_round_s == pytest.approx(0.030)
    assert p.rounds == [(pytest.approx(0.040), pytest.approx(0.020)),
                        (pytest.approx(0.030), pytest.approx(0.009))]
    by = {k: round(v * 1e3, 6) for k, v in p.idle_by_span.items()}
    assert by == {"outside": 25.0, "engine.plan": 14.0,
                  "engine.dispatch.decode": 1.0, "engine.sync": 10.0,
                  "engine.commit": 15.0, "server.publish": 5.0,
                  "engine.dispatch.chunk": 1.0}
    assert p.launches == {"jit_decode_step": [pytest.approx(0.020)],
                          "jit_chunk_step": [pytest.approx(0.009)]}
    # the loop holds the other ops: its time is not counted again
    dec = {k: round(v * 1e3, 6) for k, v in p.scope_s["jit_decode_step"]
           .items()}
    assert dec == {"attention": 10.0, "mlp": 5.0, "logits": 5.0}
    # each gap is named by the innermost program span open at its middle
    assert [(n, round(t * 1e3, 6)) for n, t in p.idle_gaps] == [
        ("server.publish", 36.0), ("other", 20.0), ("other", 15.0)]


def test_gaps_inside_a_round_take_the_innermost_span():
    tr = _tr()
    # a gap 36-39 ms, inside engine.sync inside engine.round
    tr["devices"]["/device:TPU:0"]["ops"].append(
        ("fusion.4", 39 * MS, 41 * MS, f"{ENGINE}/mlp/add"))
    gaps = dict((round(t * 1e3, 6), n)
                for n, t in spans.summarise(tr).idle_gaps)
    assert gaps[4.0] == "engine.sync"        # 35-39 ms


def test_readers_on_a_hand_made_context():
    ctx = _ctx(_tr(), decode_kv_tokens=300, decode_kv_slots=1200,
               chunk_tokens=900, chunk_token_slots=1024)
    read = {m: R.reader(m)(ctx) for m in (
        "engine_self_ms.chat", "idle_engine_share.chat",
        "idle_outside_engine_share.chat", "idle_share.chat",
        "decode_step_ms.chat", "chunk_step_ms.chat",
        "decode_attn_share.chat", "decode_kv_use_share.chat",
        "chunk_token_use_share.chat")}
    assert read["engine_self_ms.chat"] == pytest.approx((20 + 21) / 2)
    assert read["idle_engine_share.chat"] == pytest.approx(41.0)
    assert read["idle_outside_engine_share.chat"] == pytest.approx(30.0)
    assert (read["idle_engine_share.chat"]
            + read["idle_outside_engine_share.chat"]) == pytest.approx(
                read["idle_share.chat"])
    assert read["decode_step_ms.chat"] == pytest.approx(20.0)
    assert read["chunk_step_ms.chat"] == pytest.approx(9.0)
    assert read["decode_attn_share.chat"] == pytest.approx(50.0)
    assert read["decode_kv_use_share.chat"] == pytest.approx(25.0)
    assert read["chunk_token_use_share.chat"] == pytest.approx(
        100.0 * 900 / 1024)


@pytest.mark.parametrize("metric", [
    "engine_self_ms.chat", "idle_engine_share.chat",
    "idle_outside_engine_share.chat", "decode_step_ms.chat",
    "chunk_step_ms.chat", "decode_attn_share.chat",
    "decode_kv_use_share.chat", "chunk_token_use_share.chat"])
def test_a_program_without_spans_reads_nothing(metric):
    """A program that opens no spans, names every step ``jit_step``,
    scopes nothing and keeps no pad counters: each reader reads None,
    and does not raise."""
    assert R.reader(metric)(_ctx(_tr(program=False))) is None
    # nor in an untraced run of it
    untraced = NS(trace=None, win=NS(counters0={}, counters1={}))
    assert R.reader(metric)(untraced) is None


def test_a_trace_of_another_run_is_not_read(tmp_path):
    """``of`` reads the trace directory only when its window is the one
    ``ctx.trace`` summarised; here there is no trace there at all."""
    ctx = NS(trace=trace.summarise(_plain(_tr())), trace_dir=tmp_path)
    assert spans.of(ctx) is None
    assert R.reader("decode_step_ms.chat")(ctx) is None


def test_op_names_from_a_real_profile(tmp_path):
    """The scope path of an op comes from the HLO the profiler keeps for
    the program it ran in: profile a small jitted step with two scopes
    on the CPU and read the instructions' op names back."""
    import glob

    import jax
    import jax.numpy as jnp

    def scoped_step(x, w):
        with jax.named_scope("attention"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            return jax.nn.relu(y @ w).sum()

    f = jax.jit(scoped_step)
    x = jnp.ones((64, 64))
    f(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x, x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    names = spans.op_names(path)
    table = next(v for k, v in names.items()
                 if k.startswith("jit_scoped_step"))
    paths = set(table.values())
    assert any(p.endswith("attention/tanh") for p in paths)
    assert any("/mlp/" in p for p in paths)
    # an op in a launch of that program takes its instruction's path
    ins = next(k for k, v in table.items() if v.endswith("attention/tanh"))
    prog = next(k for k in names if k.startswith("jit_scoped_step"))
    ops = spans.with_scopes([(f"%{ins} = f32[64,64] tanh(...)", 5, 6),
                             ("copy.9", 20, 21)],
                            [(prog, 0, 10)], names)
    assert ops[0][3].endswith("attention/tanh")
    assert ops[1][3] == ""                   # outside any launch


def test_leaves():
    ops = [("while.2", 0, 10, ""), ("fusion.1", 0, 4, ""),
           ("call.3", 4, 10, ""), ("fusion.2", 5, 6, ""),
           ("copy.4", 6, 10, "")]
    assert [o[0] for o in spans.leaves(ops)] == ["fusion.1", "fusion.2",
                                                 "copy.4"]
