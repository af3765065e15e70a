"""KV the decode launches needed over KV they gathered, across the
window, in percent: the real context tokens of the real rows
(``engine.decode_kv_tokens``) over rows x table width x page size
(``engine.decode_kv_slots``), both padded to powers of two."""
from bench.core.readers import counter_delta


def read(ctx):
    slots = counter_delta(ctx, "engine.decode_kv_slots")
    if not slots:
        return None
    return 100.0 * counter_delta(ctx, "engine.decode_kv_tokens") / slots
