"""Front door: submitted to admitted into the engine, 90th percentile
over requests due in the window (TokenStream's own stamps)."""
from bench.core.readers import pct, waits


def read(ctx):
    return pct(waits(ctx, "submit_ms", "admitted_ms"), 90)
