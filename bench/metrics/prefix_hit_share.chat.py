"""Prompt tokens the prefix cache served, over prompt tokens admitted
in the window, in percent."""
from bench.core.readers import counter_delta


def read(ctx):
    w = ctx.win
    admitted = sum(len(r.prompt) for r in ctx.records
                   if r.admitted_ms is not None
                   and w.start_ms <= r.admitted_ms < w.end_ms)
    if not admitted:
        return None
    return 100.0 * counter_delta(ctx, "prefix_hit_tokens") / admitted
