"""Device-idle time with no ``engine.round`` span open (the server's
loop, asyncio, the traffic generator, GC), over the traced window, in
percent (averaged over the chips used).  With
``idle_engine_share.chat`` it sums to ``idle_share.chat``."""
from bench.core import spans


def read(ctx):
    p = spans.of(ctx)
    if p is None or not p.rounds or not p.window_s:
        return None
    return 100.0 * p.idle_outside_round_s / p.window_s
