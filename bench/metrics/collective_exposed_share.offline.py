"""Collective time (all-reduce / all-gather ops of the sharded steps)
during which no other op ran on that chip, over device-busy time, in
percent (averaged over chips)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_s or not t.collective_s:
        return None
    return 100.0 * t.collective_exposed_s / t.busy_s
