"""Mixture-of-Experts with shard_map expert parallelism.

Experts are sharded over the `model` mesh axis (EP); activations enter
replicated across `model` (they are batch-sharded over `data`/`pod`).
Inside `shard_map` each shard:

  1. computes router logits + global top-k (router weights replicated),
  2. builds a *capacity-bounded dispatch table* for its local experts
     with a sort-free cumsum ranking (no cross-shard scatter — the GSPMD
     scatter pathologies are avoided entirely; tokens routed to remote
     experts are simply handled by the shard that owns them, because
     every shard sees every token),
  3. gathers its tokens, runs the local expert FFNs as one batched
     einsum over the expert dim,
  4. scatter-adds weighted outputs into the local output buffer,
  5. `psum`s over `model` to combine expert contributions.

The `psum` doubles as the Megatron-style TP combine, so MoE layers cost
the same single all-reduce as a TP dense layer.  Capacity overflow drops
tokens (standard dropless-approximation; the aux load-balance loss keeps
overflow rare).  A `dense` fallback (every token through every expert,
einsum-only) exists for tiny smoke configs and as an oracle in tests.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, MoEConfig, ParallelConfig
from repro.distributed.sharding import env, shard
from .layers import cast
from .params import ParamDef


def moe_defs(cfg: ModelConfig) -> Dict[str, ParamDef]:
    m = cfg.moe
    d, ff, E = cfg.d_model, m.expert_d_ff, m.num_experts
    defs = {
        "router": ParamDef((d, E), ("embed", None), init="small"),
        "w_gate": ParamDef((E, d, ff), ("experts", "embed", "ff")),
        "w_up": ParamDef((E, d, ff), ("experts", "embed", "ff")),
        "w_down": ParamDef((E, ff, d), ("experts", "ff", "embed")),
    }
    if m.num_shared_experts:
        sff = m.expert_d_ff * m.num_shared_experts
        defs.update({
            "shared_gate": ParamDef((d, sff), ("embed", "ff")),
            "shared_up": ParamDef((d, sff), ("embed", "ff")),
            "shared_down": ParamDef((sff, d), ("ff", "embed")),
        })
    return defs


def _expert_ffn(w_gate, w_up, w_down, x, activation: str) -> jax.Array:
    """x: (E, C, d) through per-expert gated FFN."""
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g)
    return jnp.einsum("ecf,efd->ecd", act * u, w_down)


def _local_moe(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               num_experts: int, capacity: int, activation: str,
               model_axis: Optional[str],
               psum_dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """Per-shard MoE body (runs under shard_map when model_axis set).

    x: (b_local, s, d) replicated over model; expert weights are the
    LOCAL slices (E_local, ...).
    """
    b, s, d = x.shape
    t = b * s
    e_local = w_gate.shape[0]
    if model_axis is not None:
        shard_idx = jax.lax.axis_index(model_axis)
    else:
        shard_idx = 0
    e_lo = shard_idx * e_local

    xf = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)             # (t, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # aux load-balance loss terms (Switch-style)
    me = jnp.mean(probs, axis=0)                           # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, num_experts, dtype=jnp.float32), axis=1),
        axis=0)
    aux = jnp.sum(me * ce) * num_experts / top_k

    # dispatch: rank of each (token, k) within its expert, local experts only
    flat_e = top_e.reshape(-1)                             # (t*k,)
    is_local = (flat_e >= e_lo) & (flat_e < e_lo + e_local)
    local_e = jnp.where(is_local, flat_e - e_lo, e_local)  # e_local = trash
    onehot = jax.nn.one_hot(local_e, e_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot              # 1-based rank
    rank = jnp.sum(pos, axis=1) - 1                        # (t*k,)
    ok = is_local & (rank < capacity)

    # dispatch table: buf[e, c] = token index + 1 (0 = empty)
    tok_idx = jnp.repeat(jnp.arange(t), top_k)             # (t*k,)
    buf = jnp.zeros((e_local, capacity), jnp.int32)
    buf = buf.at[
        jnp.where(ok, local_e, e_local - 1),   # clamp; masked below anyway
        jnp.where(ok, rank, capacity - 1),
    ].max(jnp.where(ok, tok_idx + 1, 0))

    gathered = jnp.where((buf > 0)[..., None],
                         xf[jnp.maximum(buf - 1, 0)], 0.0)  # (E_l, C, d)
    h = _expert_ffn(w_gate, w_up, w_down, gathered.astype(w_gate.dtype),
                    activation)

    # combine: weight by router prob, scatter-add back to tokens
    flat_p = top_p.reshape(-1)
    weight = jnp.zeros((e_local, capacity), jnp.float32)
    weight = weight.at[
        jnp.where(ok, local_e, e_local - 1),
        jnp.where(ok, rank, capacity - 1),
    ].max(jnp.where(ok, flat_p, 0.0))

    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[jnp.maximum(buf - 1, 0)].add(
        h.astype(jnp.float32) * weight[..., None] * (buf > 0)[..., None])

    if model_axis is not None:
        out = jax.lax.psum(out.astype(psum_dtype), model_axis)
    return out.reshape(b, s, d).astype(x.dtype), aux


def moe_layer(cfg: ModelConfig, pcfg: ParallelConfig, p: Dict[str, jax.Array],
              x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Returns (output, aux_loss)."""
    m = cfg.moe
    e = env()
    b, s, d = x.shape
    tokens = b * s

    if pcfg.moe_impl == "dense" or e.mesh is None:
        out, aux = _dense_moe(cfg, p, x)
    else:
        mesh = e.mesh
        model_ax = "model"
        msize = mesh.shape[model_ax]
        if m.num_experts % msize != 0:
            out, aux = _dense_moe(cfg, p, x)
        else:
            bsize = int(np.prod([mesh.shape[a] for a in e.batch_axes]))
            if b % bsize == 0:
                batch_spec = P(e.batch_axes if len(e.batch_axes) > 1
                               else e.batch_axes[0])
            else:  # tiny batches (e.g. long-context decode, B=1): replicate
                batch_spec = P(None)
            cf = pcfg.moe_capacity_factor or m.capacity_factor
            cap = int(np.ceil(tokens * m.top_k / m.num_experts * cf))
            cap = max(8, min(cap, tokens))
            psum_dtype = (jnp.bfloat16 if pcfg.moe_psum_dtype == "bfloat16"
                          else jnp.float32)
            fn = functools.partial(
                _local_moe, top_k=m.top_k, num_experts=m.num_experts,
                capacity=cap, activation=cfg.activation, model_axis=model_ax,
                psum_dtype=psum_dtype)
            out, aux = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P(*batch_spec, None, None), P(None, None),
                          P(model_ax, None, None), P(model_ax, None, None),
                          P(model_ax, None, None)),
                out_specs=(P(*batch_spec, None, None), P()),
                check_vma=False,
            )(x, p["router"].astype(jnp.float32), cast(p["w_gate"]),
              cast(p["w_up"]), cast(p["w_down"]))
            aux = jnp.mean(aux)

    if m.num_shared_experts:
        g = jnp.einsum("bsd,df->bsf", x, cast(p["shared_gate"]))
        u = jnp.einsum("bsd,df->bsf", x, cast(p["shared_up"]))
        act = jax.nn.silu(g) if cfg.activation == "swiglu" else jax.nn.gelu(g)
        out = out + jnp.einsum("bsf,fd->bsd", act * u, cast(p["shared_down"]))
    return shard(out, "batch", None, None), aux


def _dense_moe(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array):
    """Oracle / fallback: every token through every expert (exact, no
    capacity drops)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32), axis=1), axis=0)
    aux = jnp.sum(me * ce) * m.num_experts / m.top_k

    h = _expert_ffn(cast(p["w_gate"]), cast(p["w_up"]), cast(p["w_down"]),
                    jnp.broadcast_to(xf.astype(cast(p["w_gate"]).dtype),
                                     (m.num_experts,) + xf.shape), cfg.activation)
    gate = jnp.zeros((b * s, m.num_experts), jnp.float32)
    gate = jnp.sum(jax.nn.one_hot(top_e, m.num_experts, dtype=jnp.float32)
                   * top_p[..., None], axis=1)
    out = jnp.einsum("te,etd->td", gate, h.astype(jnp.float32))
    return out.reshape(b, s, d).astype(x.dtype), aux
