"""99th percentile of every gap between consecutive tokens a client
received inside the window (of the traced run).  Not an end-to-end
metric: it sits on the plateau of rounds that carry a full prefill chunk
and jumps whenever a host stall pushes one gap in a hundred past it."""
from bench.core.readers import pct, window_gaps


def read(ctx):
    return pct(window_gaps(ctx), 99)
