"""KV the decode launches needed over KV they read, across the window,
in percent: the arena tokens the real rows attend over
(``engine.decode_kv_tokens``) over the token slots the launches read
(``engine.decode_kv_slots``).  On a TPU the paged kernel reads only the
pages below each row's length, so a slot count is each padded row's
length rounded up to a whole page; on the jnp reference, which gathers
the whole block table, it is rows x table width x page size.  Rows and
table widths are padded to powers of two."""
from bench.core.readers import counter_delta


def read(ctx):
    slots = counter_delta(ctx, "engine.decode_kv_slots")
    if not slots:
        return None
    return 100.0 * counter_delta(ctx, "engine.decode_kv_tokens") / slots
