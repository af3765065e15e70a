"""Time to first token, 90th percentile, over every request due in the
window, each from its due time; one with no first token by the close
counts with its wait so far."""
from bench.core.readers import pct, waits


def read(ctx):
    return pct(waits(ctx, "due_ms", "first_token"), 90)
