"""Device time of one fused decode step: ``jit_decode_step`` module time
over its launches in the traced window, in ms."""
from bench.core import spans


def read(ctx):
    return spans.mean_launch_ms(spans.of(ctx), "jit_decode_step")
