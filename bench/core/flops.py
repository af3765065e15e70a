"""Work a dense GQA decoder requires, counted from shapes.

Only what the model needs counts: prompt tokens that the prefix cache
did not serve, one decode step per generated token after the first,
causal attention over the actual context, and one row of logits per
sampled position.  Padding, duplicated pad rows and recomputation
count as zero.  ``cfg`` is the configuration file's dict.
"""

from __future__ import annotations

from typing import Dict


def layer_matmul_params(cfg: Dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvh, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    ff = cfg["intermediate_size"]
    return d * h * hd + 2 * d * kvh * hd + h * hd * d + 3 * d * ff


def token_flops(cfg: Dict) -> float:
    """Matmul FLOPs of one token through every layer (no attention
    scores, no logits)."""
    return 2.0 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)


def attention_flops(cfg: Dict, context: int) -> float:
    """QK^T and PV of one query over ``context`` keys, every layer."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def logits_flops(cfg: Dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def prefill_flops(cfg: Dict, prompt: int, cached: int) -> float:
    """Prompt positions [cached, prompt) and the first token's logits."""
    n = prompt - cached
    # position p attends to p + 1 keys: sum over p in [cached, prompt)
    keys = (prompt * (prompt + 1) - cached * (cached + 1)) // 2
    return (n * token_flops(cfg) + attention_flops(cfg, 1) * keys
            + logits_flops(cfg))


def decode_flops(cfg: Dict, context: int) -> float:
    """One generated token whose input sits at position context - 1."""
    return (token_flops(cfg) + attention_flops(cfg, context)
            + logits_flops(cfg))


def page_bytes(cfg: Dict, page_size: int, kv_dtype_bytes: int = 2) -> int:
    """Bytes of one KV page over all layers in ONE arena (K or V)."""
    return (cfg["num_hidden_layers"] * page_size
            * cfg["num_key_value_heads"] * cfg["head_dim"] * kv_dtype_bytes)


def page_op_bytes(cfg: Dict, page_size: int, inits: int,
                  copies: int) -> int:
    """HBM bytes page ops must move over both arenas: an init writes a
    page, a copy reads one and writes one."""
    return 2 * page_bytes(cfg, page_size) * (inits + 2 * copies)
