"""Prompt tokens the prefill-chunk launches computed over the token
slots they ran, across the window, in percent: real chunk tokens
(``engine.chunk_tokens``) over rows x chunk bucket
(``engine.chunk_token_slots``), both padded to powers of two."""
from bench.core.readers import counter_delta


def read(ctx):
    slots = counter_delta(ctx, "engine.chunk_token_slots")
    if not slots:
        return None
    return 100.0 * counter_delta(ctx, "engine.chunk_tokens") / slots
