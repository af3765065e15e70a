"""The dense GQA decoder's architecture module (bench/core/arch.py): the
program's configuration for the file, the weights' initialisation, the
plain float32 reference and the work each token requires, for granite-3-8b
and minitron-8b as configured in ``bench/configs``.

Pre-norm decoder layers: RMSNorm, grouped-query attention with
GPT-NeoX half-rotation RoPE and a causal softmax scaled by
1/sqrt(head_dim), a residual add, RMSNorm, a SwiGLU MLP
(silu(x W_gate) * (x W_up)) W_down, a residual add; then a final
RMSNorm and logits against the output table (the token table when
tied).  Everything is computed in float32 with
``default_matmul_precision("highest")``, one layer at a time, so that
a model whose float32 copy exceeds the chip still fits: each layer's
weights are drawn again from the seed (``bench.core.weights``), bit
for bit what the benchmark handed the program, and nothing is taken
from the program itself.

``quant=True`` is the control: every matmul input (weights per
tensor, activations per row) is rounded to float8_e4m3 with a scale,
the precision one step below the bfloat16 the configuration states.

The FLOP counts take only what the model needs: prompt tokens that the
prefix cache did not serve, one decode step per generated token after
the first, causal attention over the actual context, and one row of
logits per sampled position.  Padding, duplicated pad rows and
recomputation count as zero.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.core import weights as W
from bench.core.arch import Refused

# the parameter paths of the served model's tree, and which are stacked
# over layers (the benchmark draws the program's tree under these names)
ATTN = "group0/0_attn"
MLP = "group0/1_mlp"
PAD = 1024               # sequences are padded to a multiple of this
ROWS = 512               # and the positions read to a multiple of this


def model_config(cfg: Dict):
    from repro.configs import ModelConfig
    if cfg["model_type"] != "dense_gqa" or cfg["hidden_act"] != "silu":
        raise Refused(f"{cfg['name']}: no engine path for "
                      f"{cfg['model_type']}/{cfg['hidden_act']}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation="swiglu", norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])


# every leaf of this architecture (norms, token tables, attention, MLP)
# takes the common rule
leaf_std = W.leaf_std


def _dims(cfg: Dict) -> Tuple[int, int, int, int, int, int]:
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"])


def layer_shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    d, h, kvh, hd, ff, _ = _dims(cfg)
    return {f"{ATTN}/norm": (d,),
            f"{ATTN}/attn/wq": (d, h, hd), f"{ATTN}/attn/wk": (d, kvh, hd),
            f"{ATTN}/attn/wv": (d, kvh, hd), f"{ATTN}/attn/wo": (h, hd, d),
            f"{MLP}/norm": (d,), f"{MLP}/mlp/up": (d, ff),
            f"{MLP}/mlp/gate": (d, ff), f"{MLP}/mlp/down": (ff, d)}


def _draw(words, path, shape, d, layer=None):
    return W.draw_leaf(words, path, shape, leaf_std(path, shape, d),
                       stacked=layer is not None, dtype=jnp.bfloat16,
                       layer=layer).astype(jnp.float32)


def _qdq(x, axis=None):
    """Round to float8_e4m3 under a max-abs scale (per tensor, or per
    slice along ``axis``) and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("shapes", "d"))
def _draw_layer(words, layer, *, shapes, d):
    return {p: _draw(words, p, s, d, layer) for p, s in shapes}


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _layer(x, w, *, eps, theta, quant):
    """One decoder layer over one padded sequence x (S, d)."""
    q8 = (lambda a, axis=None: _qdq(a, axis)) if quant else \
        (lambda a, axis=None: a)
    S = x.shape[0]
    pos = jnp.arange(S)
    wq, wk, wv, wo = (q8(w[f"{ATTN}/attn/{n}"]) for n in ("wq", "wk", "wv",
                                                          "wo"))
    h = q8(_rms(x, w[f"{ATTN}/norm"], eps), -1)
    q = _rope(jnp.einsum("sd,dhk->shk", h, wq), pos, theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, wk), pos, theta)
    v = jnp.einsum("sd,dhk->shk", h, wv)
    hq, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(S, kvh, hq // kvh, hd)
    s = jnp.einsum("sngd,tnd->ngst", qg, k) * (hd ** -0.5)
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngst,tnd->sngd", p, v).reshape(S, hq, hd)
    x = x + jnp.einsum("shk,hkd->sd", q8(o, -1), wo)
    h = q8(_rms(x, w[f"{MLP}/norm"], eps), -1)
    gate, up = q8(w[f"{MLP}/mlp/gate"]), q8(w[f"{MLP}/mlp/up"])
    a = jax.nn.silu(h @ gate) * (h @ up)
    return x + q8(a, -1) @ q8(w[f"{MLP}/mlp/down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant", "d", "shape",
                                             "path"))
def _logits(x, rows, words, g, *, eps, quant, d, shape, path):
    """Logits at ``rows`` of x, against the output table drawn here."""
    table = _draw(words, path, shape, d)
    h = _rms(x[rows], g, eps)
    if quant:
        h, table = _qdq(h, -1), _qdq(table)
    return h @ table.T


def _embed(words, toks, *, shape, d):
    return _draw(words, "embed/tok", shape, d)[toks]


_embed = jax.jit(_embed, static_argnames=("shape", "d"))


def logits_at(cfg: Dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], quant: bool = False
              ) -> List[np.ndarray]:
    """For each token sequence, the logits (len(positions[i]), vocab)
    predicting the token after each listed position, on the host.  The
    positions are padded to a multiple of ``ROWS`` (the last repeated),
    so one program per sequence bucket serves every request length."""
    d, _, _, _, _, V = _dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    L = cfg["num_hidden_layers"]
    words = W.seed_words(seed)
    with jax.default_matmul_precision("highest"):
        xs = []
        for toks in seqs:
            S = -(-len(toks) // PAD) * PAD
            padded = np.zeros(S, np.int32)
            padded[:len(toks)] = toks
            xs.append(_embed(words, jnp.asarray(padded), shape=(V, d), d=d))
        shapes = tuple(layer_shapes(cfg).items())
        for layer in range(L):
            w = _draw_layer(words, jnp.uint32(layer), shapes=shapes, d=d)
            xs = [_layer(x, w, eps=eps, theta=theta, quant=quant) for x in xs]
            del w
        table = "embed/tok" if cfg["tie_word_embeddings"] else "embed/out"
        g = jnp.ones((d,), jnp.float32)            # final norm: ones
        out = []
        for x, pos in zip(xs, positions):
            n = len(pos)
            rows = np.full(-(-n // ROWS) * ROWS, pos[-1], np.int32)
            rows[:n] = pos
            out.append(np.asarray(_logits(
                x, jnp.asarray(rows), words, g, eps=eps, quant=quant, d=d,
                shape=(V, d), path=table))[:n])
        return out


def layer_matmul_params(cfg: Dict) -> int:
    d, h, kvh, hd, ff, _ = _dims(cfg)
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
