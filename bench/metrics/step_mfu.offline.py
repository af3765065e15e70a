"""Whole-step utilisation: FLOPs the model required for the tokens made
in the traced window (the architecture module's counts) over device-busy
seconds x chips x peak bf16 FLOP/s, in percent."""
from bench.core.readers import required_flops


def read(ctx):
    t = ctx.trace
    if t is None or not t.busy_s:
        return None
    lo, hi = ctx.win.trace_ms
    need = required_flops(ctx, lo, hi)
    peak = ctx.chips * ctx.peak["bf16_flops_per_s"]
    return 100.0 * need / (t.busy_s * peak) if need else None
