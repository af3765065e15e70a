"""Share of the fused decode step spent in attention: device time of
the leaf ops under the ``attention`` named scope inside
``jit_decode_step`` launches, over those launches' time, in percent.
The scope runs from the q/k/v projections through the block-table
gather and the attention kernel to the output projection; the layer
loop's own slice of each layer's arena carries no scope."""
from bench.core import spans


def read(ctx):
    return spans.scope_share(spans.of(ctx), "jit_decode_step", "attention")
