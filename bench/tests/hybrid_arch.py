"""A tiny hybrid architecture module, for the test that a new
architecture joins the benchmark as files alone: attention every second
layer, Mamba-2 (SSD) state layers between, and a 4-expert top-2 MoE in
every second feed-forward.

It tests the harness's plumbing and is no model's reference.  Its
``logits_at`` runs the program's own unpaged forward
(``repro.models.transformer.forward``) on the weights the harness drew,
with activations in float32 and every matmul at ``highest`` precision:
independent of the paged engine under test, but not of the program.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import weights as W
from bench.core.arch import Refused

PAD = 128                # sequences are padded to a multiple of this


def model_config(cfg: Dict):
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation="swiglu", norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        attn_every=cfg["attn_layer_period"],
        moe=MoEConfig(num_experts=cfg["num_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      expert_d_ff=cfg["expert_intermediate_size"]),
        ssm=SSMConfig(state_dim=cfg["mamba_d_state"],
                      head_dim=cfg["mamba_head_dim"],
                      expand=cfg["mamba_expand"],
                      conv_width=cfg["mamba_d_conv"],
                      chunk_size=cfg["mamba_chunk_size"]))


def leaf_std(path: str, shape: Sequence[int], d_model: int) -> float:
    """State-layer and expert leaves; the rest by the common rule."""
    name = path.rsplit("/", 1)[-1]
    if name in ("in_proj", "router", "w_gate", "w_up"):
        return 1.0 / math.sqrt(d_model)
    if name in ("out_proj", "w_down", "conv_w"):     # (inputs, outputs)
        return 1.0 / math.sqrt(shape[-2])
    if name in ("conv_b", "a_log", "dt_bias"):
        return 0.5
    if name == "d_skip":
        return 0.0                                    # ones
    return W.leaf_std(path, shape, d_model)


def logits_at(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], quant: bool = False
              ) -> List[np.ndarray]:
    if quant:
        raise Refused(f"{cfg['name']}: its module has no control")
    from repro.configs.base import ParallelConfig
    from repro.models import layers
    from repro.models import transformer as T
    mcfg = model_config(cfg)
    params = W.draw_tree(T.model_defs(mcfg), seed, cfg["hidden_size"],
                         leaf_std)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    pcfg = ParallelConfig(attention_impl="naive", remat="none",
                          moe_impl="dense")
    fwd = jax.jit(lambda p, t: T.forward(mcfg, pcfg, p, {"tokens": t},
                                         mode="train")[0][0])
    out = []
    with mock.patch.object(layers, "COMPUTE_DTYPE", jnp.float32), \
            jax.default_matmul_precision("highest"):
        for toks, pos in zip(seqs, positions):
            # causal: the padding after a sequence changes none of its
            # positions, and one program serves each length bucket
            padded = np.zeros(-(-len(toks) // PAD) * PAD, np.int32)
            padded[:len(toks)] = toks
            out.append(np.asarray(fwd(params, jnp.asarray(padded)[None]))
                       [np.asarray(pos)])
    return out


def _counts(cfg: Dict) -> Dict[str, int]:
    """Sublayers of each kind, as the program lays a hybrid out."""
    L, period = cfg["num_hidden_layers"], cfg["attn_layer_period"]
    n_moe = (L // period) * (period // 2)
    return {"attn": L // period, "mamba": L - L // period, "moe": n_moe,
            "mlp": L - n_moe}


def _token_flops(cfg: Dict, context: int) -> float:
    """One token through every layer, attending over ``context`` keys,
    and its row of logits."""
    d, h, kvh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    d_in = cfg["mamba_expand"] * d
    N, P = cfg["mamba_d_state"], cfg["mamba_head_dim"]
    H, ch = d_in // P, d_in + 2 * cfg["mamba_d_state"]
    n = _counts(cfg)
    attn = 2 * (d * h * hd + 2 * d * kvh * hd + h * hd * d) \
        + 4 * h * hd * context
    # projections, the depthwise conv, and the state's update and read
    mamba = 2 * (d * (2 * d_in + 2 * N + H) + d_in * d) \
        + 2 * cfg["mamba_d_conv"] * ch + 4 * H * P * N
    mlp = 6 * d * cfg["intermediate_size"]
    moe = 2 * d * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * 6 * d * cfg["expert_intermediate_size"]
    return (n["attn"] * attn + n["mamba"] * mamba + n["mlp"] * mlp
            + n["moe"] * moe + 2.0 * d * cfg["vocab_size"])


def prefill_flops(cfg: Dict, prompt: int, cached: int) -> float:
    """Prompt positions [cached, prompt), each with its logits row left
    out but the last."""
    logits = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return sum(_token_flops(cfg, p + 1) for p in range(cached, prompt)) \
        - logits * (prompt - cached - 1)


def decode_flops(cfg: Dict, context: int) -> float:
    return _token_flops(cfg, context)


def page_op_bytes(cfg: Dict, page_size: int, inits: int,
                  copies: int) -> int:
    """Both KV arenas, over the attention layers alone: an init writes a
    page, a copy reads one and writes one."""
    page = (_counts(cfg)["attn"] * page_size * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)
    return 2 * page * (inits + 2 * copies)
