"""Device time of one fused prefill-chunk step: ``jit_chunk_step``
module time over its launches in the traced window, in ms."""
from bench.core import spans


def read(ctx):
    return spans.mean_launch_ms(spans.of(ctx), "jit_chunk_step")
