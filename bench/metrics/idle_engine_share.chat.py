"""Device-idle time while an ``engine.round`` span is open, over the
traced window, in percent (averaged over the chips used): the chip
waiting on the engine's own host work."""
from bench.core import spans


def read(ctx):
    p = spans.of(ctx)
    if p is None or not p.rounds or not p.window_s:
        return None
    return 100.0 * p.idle_in_round_s / p.window_s
