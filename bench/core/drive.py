"""Drive a served engine with a cell's traffic and record what the
client sees.

Host clock throughout is ``time.perf_counter`` in milliseconds, the
clock ``repro.serving.server.TokenStream`` stamps tokens with.  A
request's latency is taken from its due time, so a stalled generator
or a late admission counts against the server.

In a traced run the benchmark's own spans (``round.prefill`` /
``round.decode`` around each engine round, ``admit``, ``generator``,
``pim_flush``) go into the profiler trace as ``TraceAnnotation``s, and
``bench.mark`` spans bound the traced part of the window.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


def now_ms() -> float:
    return time.perf_counter() * 1e3


@dataclass
class Record:
    rid: int
    prompt: np.ndarray
    max_new: int
    due_ms: float
    submit_ms: float = 0.0
    admitted_ms: Optional[float] = None
    cached: int = 0                      # prompt tokens from the prefix cache
    token_ms: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    rejected: bool = False


@dataclass
class Window:
    start_ms: float
    end_ms: float
    counters0: Dict = field(default_factory=dict)
    counters1: Dict = field(default_factory=dict)
    trace_ms: Optional[tuple] = None     # (start, end) of the traced part
    trace_counters0: Dict = field(default_factory=dict)
    trace_counters1: Dict = field(default_factory=dict)
    max_batch: int = 0
    compiles: int = 0
    # the most pages one page-op launch took, by kind (lead-in included)
    max_pages: Dict = field(default_factory=dict)
    gen_late_ms: List[float] = field(default_factory=list)
    # (ms, requests due and not finished), about once a second
    backlog: List[tuple] = field(default_factory=list)

    def backlog_slope(self) -> Optional[float]:
        """Growth of the due-but-unfinished backlog across the window,
        in requests per second (least squares); about 0 below the
        knee."""
        pts = [(t, n) for t, n in self.backlog if t >= self.start_ms]
        if len(pts) < 3:
            return None
        t, n = np.asarray(pts, float).T
        return float(np.polyfit((t - t[0]) / 1e3, n, 1)[0])


def counters(eng) -> Dict:
    c = dict(eng.cache.stats)
    c.update({f"engine.{k}": v for k, v in eng.stats.items()})
    c.update({f"launch.{k}": v
              for k, v in eng.cache.queue.launches_by_kind.items()})
    return c


class Tracer:
    """Profiles the last ``seconds`` of the window.  Stopping the
    profiler holds the host for seconds while it writes the trace, so
    the traced part ends with the window: the stall falls after the
    close, where it delays nothing that is measured."""

    def __init__(self, log_dir: Optional[str], seconds: float, eng):
        self.log_dir, self.seconds, self.eng = log_dir, seconds, eng
        self.on = False
        self.span: Optional[tuple] = None
        self.counters0: Dict = {}
        self.counters1: Dict = {}

    def start(self) -> None:
        if self.log_dir is None:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.host_tracer_level = 1       # TraceAnnotation spans only
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.counters0 = counters(self.eng)
        with jax.profiler.TraceAnnotation("bench.mark"):
            t = now_ms()
        self.on, self.span = True, (t, None)

    def maybe_stop(self, force: bool = False) -> None:
        if not self.on:
            return
        if force or now_ms() >= self.span[0] + self.seconds * 1e3:
            with jax.profiler.TraceAnnotation("bench.mark"):
                t = now_ms()
            self.counters1 = counters(self.eng)
            jax.profiler.stop_trace()
            self.on, self.span = False, (self.span[0], t)

    def tick(self, t: float, win: "Window") -> None:
        """Called through the window: start the profiler ``seconds``
        before the close, and stop it once they have passed."""
        if self.span is None:
            if t >= win.end_ms - self.seconds * 1e3:
                self.start()
        else:
            self.maybe_stop()

    def into(self, win: "Window") -> None:
        win.trace_ms = self.span
        win.trace_counters0, win.trace_counters1 = (self.counters0,
                                                    self.counters1)


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def _round_kind(eng) -> str:
    return "round.prefill" if eng.prefill_backlog_tokens() else "round.decode"


def _wrap(obj, name: str, span: Callable[[], str]) -> None:
    """Put a profiler span around an instance method, from outside."""
    inner = getattr(obj, name)

    def wrapped(*a, **kw):
        with annotate(span()):
            return inner(*a, **kw)

    setattr(obj, name, wrapped)


def _note_cached(eng, recs: Dict[int, "Record"]):
    """Wrap the cache's ``create`` so each request's prefix-cache hit
    (the prompt tokens it did not compute) is read as it is admitted.
    Returns the undo."""
    inner = eng.cache.create

    def create(seq_id, prompt_len, *a, **kw):
        seq = inner(seq_id, prompt_len, *a, **kw)
        if seq_id in recs:
            recs[seq_id].cached = (seq.shared_prefix_pages
                                   * eng.cache.page_size)
        return seq

    eng.cache.create = create
    return lambda: setattr(eng.cache, "create", inner)


def _note_page_ops(most: Dict[str, int]):
    """Wrap the RowClone page-op entry points so the largest page count
    a launch of each kind took is kept in ``most``: each count is a
    program of its own, which warm-up has to have covered.  Returns
    the undo."""
    from repro.kernels.rowclone import ops as rc_ops
    inner = {k: getattr(rc_ops, f"pim_page_{k}_batched")
             for k in ("init", "copy")}

    def wrap(kind):
        def op(arena, *a, **kw):
            n = int(a[0].shape[0])           # dst (init) or src (copy)
            most[kind] = max(most.get(kind, 0), n)
            return inner[kind](arena, *a, **kw)
        return op

    for k in inner:
        setattr(rc_ops, f"pim_page_{k}_batched", wrap(k))
    return lambda: [setattr(rc_ops, f"pim_page_{k}_batched", f)
                    for k, f in inner.items()]


def open_loop(eng, srv_kw: Dict, reqs, lead_s: float, seconds: float,
              tracer: Tracer, clock) -> tuple:
    """Chat traffic through ``AsyncServer``: every request is submitted
    at its due time (``reqs`` sorted by due offset); the window opens
    ``lead_s`` after the traffic starts and lasts ``seconds``.  Nothing
    is drained after it: requests still running are left where they
    are.  Returns (records, window)."""
    from repro.serving.server import AsyncServer

    recs: Dict[int, Record] = {}
    streams = {}
    if tracer.log_dir is not None:
        _wrap(eng, "step", lambda: _round_kind(eng))
        _wrap(eng.cache.lib, "flush", lambda: "pim_flush")

    async def main() -> Window:
        srv = AsyncServer(eng, **srv_kw)
        if tracer.log_dir is not None:
            _wrap(srv, "_admit", lambda: "admit")
        srv.start()
        t0 = now_ms()
        win = Window(t0 + lead_s * 1e3, t0 + (lead_s + seconds) * 1e3)

        async def watch():
            opened = False
            while True:
                t = now_ms()
                if not opened and t >= win.start_ms:
                    opened = True
                    win.counters0 = counters(eng)
                    win.compiles = -clock.count
                if opened:
                    tracer.tick(t, win)
                    win.max_batch = max(win.max_batch, len(eng.active))
                if not win.backlog or t >= win.backlog[-1][0] + 1e3:
                    win.backlog.append((t, sum(
                        1 for x in streams.values() if x.finished_ms is None)))
                if t >= win.end_ms:
                    return
                await asyncio.sleep(0.002)

        watcher = asyncio.get_running_loop().create_task(watch())
        for r in reqs:
            due = t0 + r.due_s * 1e3
            if due >= win.end_ms:
                break
            wait = (due - now_ms()) / 1e3
            if wait > 0:
                await asyncio.sleep(wait)
            with annotate("generator"):
                sub = now_ms()
                recs[r.rid] = Record(r.rid, r.prompt, r.max_new_tokens, due,
                                     sub)
                streams[r.rid] = await srv.submit(
                    r.prompt, max_new_tokens=r.max_new_tokens,
                    temperature=0.0, req_id=r.rid)
            if due >= win.start_ms:
                win.gen_late_ms.append(sub - due)
        await watcher
        tracer.maybe_stop(force=True)
        tracer.into(win)
        win.counters1 = counters(eng)
        win.compiles += clock.count
        # leaving asyncio.run cancels the server's loop between rounds
        return win

    undo = _note_cached(eng, recs)
    most: Dict[str, int] = {}
    undo_pages = _note_page_ops(most)
    try:
        win = asyncio.run(main())
    finally:
        undo()
        undo_pages()
    win.max_pages = most
    for rid, st in streams.items():
        rec = recs[rid]
        rec.admitted_ms = st.admitted_ms
        rec.token_ms = list(st.token_ms)
        rec.tokens = list(st.tokens)
        rec.done = st.req.done
        rec.rejected = st.rejected
    return list(recs.values()), win


def closed_loop(eng, pool, concurrency: int, fill_per_round: int,
                lead_s: float, seconds: float, tracer: Tracer,
                clock) -> tuple:
    """Offline generation: ``concurrency`` requests in flight, a
    finished one replaced before the next round (the lead-in fills the
    batch ``fill_per_round`` at a time).  Tokens are stamped when the
    round that made them returns.  Returns (records, window)."""
    from repro.serving.engine import Request

    recs: Dict[int, Record] = {}
    live: Dict[int, Request] = {}
    undo = _note_cached(eng, recs)
    t0 = now_ms()
    win = Window(t0 + lead_s * 1e3, t0 + (lead_s + seconds) * 1e3)
    undo_pages = _note_page_ops(win.max_pages)
    opened = False
    try:
        while True:
            t = now_ms()
            if not opened and t >= win.start_ms:
                opened = True
                win.counters0 = counters(eng)
                win.compiles = -clock.count
            if t >= win.end_ms:
                break
            if opened:
                tracer.tick(t, win)
            room = concurrency - len(live)
            if not opened:
                room = min(room, fill_per_round)
            with annotate("generator"):
                for _ in range(room):
                    r = pool.take()
                    recs[r.rid] = Record(r.rid, r.prompt, r.max_new_tokens,
                                         t, t, admitted_ms=t)
                    live[r.rid] = Request(r.rid, r.prompt,
                                          max_new_tokens=r.max_new_tokens,
                                          temperature=0.0)
                    eng.submit(live[r.rid])
            kind = _round_kind(eng)
            with annotate(kind):
                eng.step()
            t = now_ms()
            win.max_batch = max(win.max_batch, len(eng.active))
            for rid, req in list(live.items()):
                rec = recs[rid]
                new = req.out_tokens[len(rec.tokens):]
                rec.tokens += new
                rec.token_ms += [t] * len(new)
                if req.done:
                    rec.done = True
                    del live[rid]
        tracer.maybe_stop(force=True)
        tracer.into(win)
        win.counters1 = counters(eng)
        win.compiles += clock.count
    finally:
        undo()
        undo_pages()
    return list(recs.values()), win
