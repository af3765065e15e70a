"""Engine self time, from the program's own spans: per ``engine.round``
span in the traced window, the span minus the device-busy time inside
it; the mean over rounds, in ms (the in-program twin of
``sched_self_ms.chat``, whose span the benchmark opens around
``engine.step()``)."""
from bench.core import spans


def read(ctx):
    p = spans.of(ctx)
    if p is None or not p.rounds:
        return None
    return 1e3 * sum(s - b for s, b in p.rounds) / len(p.rounds)
