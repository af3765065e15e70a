"""Every output token stamped inside the window, over the window."""
from bench.core.readers import tokens_between


def read(ctx):
    n = tokens_between(ctx, ctx.win.start_ms, ctx.win.end_ms)
    return n / ctx.seconds if n else None
