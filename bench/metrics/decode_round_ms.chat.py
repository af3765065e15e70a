"""Device time of an engine round that carried no prefill chunk, mean
over such rounds in the traced window, in ms.  Rounds are told apart
by the benchmark's own span names."""
from bench.core.readers import rounds


def read(ctx):
    rs = rounds(ctx, "round.decode")
    return 1e3 * sum(b for _, _, b in rs) / len(rs) if rs else None
