"""Arithmetic shared by the metric readers in ``bench/metrics``.

A reader is ``read(ctx) -> float | None``: None when the run holds
nothing for it to read (the harness then leaves the metric out).
``ctx`` carries the run: ``cfg`` (configuration file), ``arch`` (its
architecture module, bench/core/arch.py), ``dep`` (its deployment),
``chips``, ``peak`` (bench/core/peaks.py), ``setup_s``, ``seconds``,
``records`` (bench/core/drive.Record), ``win`` (drive.Window), ``trace``
(bench/core/trace.Summary or None) and ``trace_dir`` (where the profiler
wrote the trace, or None).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if values else None


def due_in_window(ctx):
    w = ctx.win
    return [r for r in ctx.records if w.start_ms <= r.due_ms < w.end_ms]


def waits(ctx, start: str, end: str) -> List[float]:
    """end - start per request due in the window; one that has not
    reached ``end`` by the close counts with its wait so far."""
    out = []
    for r in due_in_window(ctx):
        t0 = getattr(r, start)
        t1 = end_time(r, end)
        out.append((t1 if t1 is not None and t1 <= ctx.win.end_ms
                    else ctx.win.end_ms) - t0)
    return out


def end_time(r, end: str):
    if end == "first_token":
        return r.token_ms[0] if r.token_ms else None
    return getattr(r, end)


def window_gaps(ctx) -> List[float]:
    """Every gap between consecutive tokens of a request that closes in
    the window, and for a request still unfinished at the close the
    gap still open then, from its last token to the close."""
    lo, hi = ctx.win.start_ms, ctx.win.end_ms
    out = []
    for r in ctx.records:
        t = [x for x in r.token_ms if x < hi]
        out += [b - a for a, b in zip(t, t[1:]) if lo <= b]
        if t and (len(t) < len(r.token_ms) or not r.done):
            out.append(hi - t[-1])
    return out


def tokens_between(ctx, lo: float, hi: float) -> int:
    return sum(1 for r in ctx.records for t in r.token_ms if lo <= t < hi)


def required_flops(ctx, lo: float, hi: float) -> float:
    """FLOPs the model required for the tokens stamped in [lo, hi), as
    the architecture module counts them."""
    arch, total = ctx.arch, 0.0
    for r in ctx.records:
        P = len(r.prompt)
        for j, t in enumerate(r.token_ms):
            if lo <= t < hi:
                total += (arch.prefill_flops(ctx.cfg, P, r.cached) if j == 0
                          else arch.decode_flops(ctx.cfg, P + j))
    return total


def counter_delta(ctx, key: str, traced: bool = False) -> float:
    a, b = ((ctx.win.trace_counters0, ctx.win.trace_counters1) if traced
            else (ctx.win.counters0, ctx.win.counters1))
    return b.get(key, 0) - a.get(key, 0)


def rounds(ctx, kind: Optional[str] = None):
    if ctx.trace is None:
        return []
    return [r for r in ctx.trace.rounds if kind is None or r[0] == kind]
