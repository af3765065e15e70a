"""Scheduler self time: per engine round in the traced window, the
benchmark's span around the round minus the device-busy time inside
it; the mean over rounds, in ms."""
from bench.core.readers import rounds


def read(ctx):
    rs = rounds(ctx)
    return 1e3 * sum(s - b for _, s, b in rs) / len(rs) if rs else None
