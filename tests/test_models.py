"""Per-architecture smoke tests + model-math validation.

Every assigned arch: reduced config, one forward + one train step on CPU,
shape and finiteness asserts.  Plus: prefill/decode == full forward,
flash-vjp == naive autodiff, SSD == naive recurrence, MoE dispatch ==
dense oracle, fused LM head == naive xent.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCHS, OptimizerConfig, ParallelConfig, reduced)
from repro.models import transformer as T
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.params import init_params, param_count
from repro.training.train_step import make_train_step

PCFG = ParallelConfig(remat="none", attention_impl="naive", moe_impl="dense")
PCFG_CHUNK = ParallelConfig(remat="full", attention_impl="chunked",
                            attention_chunk=16, moe_impl="dense")


def make_batch(r, key, B=2, S=32):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, r.vocab_size)}
    labels = jnp.roll(batch["tokens"], -1, axis=1)
    if r.family == "vlm":
        fd = r.frontend_dim or r.d_model
        batch["patch_embeds"] = jnp.ones((B, r.num_patch_tokens, fd), jnp.float32)
        batch["tokens"] = batch["tokens"][:, : S - r.num_patch_tokens]
        labels = jnp.concatenate(
            [jnp.full((B, r.num_patch_tokens), -100, jnp.int32),
             labels[:, : S - r.num_patch_tokens]], axis=1)
    if r.family == "encdec":
        fd = r.frontend_dim or r.d_model
        batch["frames"] = jnp.ones((B, S // 2, fd), jnp.float32)
        batch["tokens"] = batch["tokens"][:, : S // 2]
        labels = labels[:, : S // 2]
    batch["labels"] = labels
    return batch


# the two giant hybrid/MoE archs take 15-60s per case even reduced;
# keep them out of the default tier-1 run (CI runs them under -m slow)
_HEAVY_ARCHS = {"jamba-1.5-large-398b", "deepseek-v2-236b"}


def _arch_params(archs):
    return [pytest.param(a, marks=pytest.mark.slow) if a in _HEAVY_ARCHS
            else a for a in archs]


@pytest.mark.parametrize("arch", _arch_params(sorted(ARCHS)))
class TestArchSmoke:
    def test_forward_shapes_finite(self, arch, key):
        r = reduced(ARCHS[arch])
        params = init_params(T.model_defs(r), key)
        batch = make_batch(r, key)
        logits, aux = T.forward(r, PCFG, params, batch, mode="train")
        assert logits.shape[0] == 2 and logits.shape[-1] == r.vocab_size
        assert bool(jnp.isfinite(logits).all())
        assert bool(jnp.isfinite(aux))

    def test_one_train_step(self, arch, key):
        r = reduced(ARCHS[arch])
        params = init_params(T.model_defs(r), key)
        init_state, step = make_train_step(
            r, PCFG_CHUNK, OptimizerConfig(warmup_steps=1, total_steps=4))
        state = init_state(params)
        batch = make_batch(r, key)
        state, metrics = jax.jit(step)(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert np.isfinite(float(metrics["grad_norm"]))
        # params actually changed
        before = jax.tree.leaves(params)[0]
        after = jax.tree.leaves(state["params"])[0]
        assert not np.allclose(np.asarray(before), np.asarray(after))

    def test_param_count_close_to_analytic(self, arch):
        cfg = ARCHS[arch]
        defs = T.model_defs(cfg)
        actual = param_count(defs)
        analytic = cfg.param_count()
        assert abs(actual - analytic) / analytic < 0.05, (actual, analytic)


@pytest.mark.parametrize("arch", _arch_params(
    ["granite-3-8b", "gemma-2b", "stablelm-3b", "minitron-8b", "mamba2-1.3b",
     "jamba-1.5-large-398b", "llama4-scout-17b-a16e", "seamless-m4t-medium"]))
def test_prefill_decode_matches_full_forward(arch, key):
    r = reduced(ARCHS[arch])
    params = init_params(T.model_defs(r), key)
    B, S, MAX = 2, 16, 32
    toks = jax.random.randint(key, (B, S + 2), 0, r.vocab_size)
    extra = {}
    if r.family == "encdec":
        fd = r.frontend_dim or r.d_model
        extra["frames"] = jax.random.normal(key, (B, 8, fd))
    ref, _ = T.forward(r, PCFG, params, {"tokens": toks, **extra}, mode="train")
    cache = T.init_cache(r, B, MAX, enc_len=8 if r.family == "encdec" else 0)
    lg, cache, _ = T.forward(r, PCFG, params, {"tokens": toks[:, :S], **extra},
                             mode="prefill", cache=cache,
                             lengths=jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(ref[:, S - 1], np.float32),
                               rtol=3e-2, atol=3e-2)
    for t in range(2):
        pos = S + t
        lg, cache = T.forward(r, PCFG, params,
                              {"tokens": toks[:, pos:pos + 1]}, mode="decode",
                              cache=cache, write_pos=jnp.asarray(pos),
                              lengths=jnp.full((B,), pos + 1, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(ref[:, pos], np.float32),
                                   rtol=3e-2, atol=3e-2)


def test_mla_decode_absorbed_matches(key):
    # looser tolerance: absorbed decode reorders bf16 matmuls
    r = reduced(ARCHS["deepseek-v2-236b"])
    params = init_params(T.model_defs(r), key)
    B, S, MAX = 2, 16, 32
    toks = jax.random.randint(key, (B, S + 2), 0, r.vocab_size)
    ref, _ = T.forward(r, PCFG, params, {"tokens": toks}, mode="train")
    cache = T.init_cache(r, B, MAX)
    lg, cache, _ = T.forward(r, PCFG, params, {"tokens": toks[:, :S]},
                             mode="prefill", cache=cache,
                             lengths=jnp.full((B,), S, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(ref[:, S - 1], np.float32),
                               rtol=3e-2, atol=3e-2)
    lg, _ = T.forward(r, PCFG, params, {"tokens": toks[:, S:S + 1]},
                      mode="decode", cache=cache, write_pos=jnp.asarray(S),
                      lengths=jnp.full((B,), S + 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(ref[:, S], np.float32),
                               rtol=8e-2, atol=8e-2)


class TestAttentionMath:
    def test_flash_fwd_bwd_vs_naive(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 50, 4, 16)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 50, 2, 16)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 50, 2, 16)).astype(np.float32))
        lens = jnp.asarray([30, 50], jnp.int32)

        def lc(q, k, v):
            return jnp.sum(A.chunked_attention(
                q, k, v, causal=True, chunk_q=16, chunk_k=16, lengths=lens) ** 2)

        def ln(q, k, v):
            return jnp.sum(A.naive_attention(q, k, v, causal=True,
                                             lengths=lens) ** 2)

        np.testing.assert_allclose(lc(q, k, v), ln(q, k, v), rtol=1e-5)
        gc = jax.grad(lc, (0, 1, 2))(q, k, v)
        gn = jax.grad(ln, (0, 1, 2))(q, k, v)
        for a, b in zip(gc, gn):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_decode_attention_vs_naive(self, rng):
        q = jnp.asarray(rng.normal(size=(2, 1, 4, 16)).astype(np.float32))
        kc = jnp.asarray(rng.normal(size=(2, 24, 2, 16)).astype(np.float32))
        vc = jnp.asarray(rng.normal(size=(2, 24, 2, 16)).astype(np.float32))
        lens = jnp.asarray([10, 24], jnp.int32)
        out = A.decode_attention(q, kc, vc, lens)
        expect = A.naive_attention(q, kc, vc, causal=False, lengths=lens)
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-5)


class TestSSD:
    def test_chunked_equals_naive_recurrence(self, rng):
        b, s, h, p, n, Q = 2, 37, 3, 4, 8, 8
        x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
        dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)).astype(np.float32))
        Amat = -jnp.asarray(rng.uniform(0.5, 2.0, h).astype(np.float32))
        B = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
        C = jnp.asarray(rng.normal(size=(b, s, n)).astype(np.float32))
        y, final = S._ssd_chunked(x, dt, Amat, B, C, Q)

        # naive per-step recurrence
        state = np.zeros((b, h, p, n), np.float32)
        ys = np.zeros((b, s, h, p), np.float32)
        for t in range(s):
            dA = np.exp(np.asarray(dt[:, t]) * np.asarray(Amat)[None])
            dBx = np.einsum("bn,bh,bhp->bhpn", np.asarray(B[:, t]),
                            np.asarray(dt[:, t]), np.asarray(x[:, t]))
            state = state * dA[:, :, None, None] + dBx
            ys[:, t] = np.einsum("bn,bhpn->bhp", np.asarray(C[:, t]), state)
        np.testing.assert_allclose(np.asarray(y, np.float32), ys, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(final), state, rtol=2e-4, atol=2e-4)

    def test_prefill_state_continues_decode(self, key):
        r = reduced(ARCHS["mamba2-1.3b"])
        params = init_params(T.model_defs(r), key)
        toks = jax.random.randint(key, (1, 20), 0, r.vocab_size)
        ref, _ = T.forward(r, PCFG, params, {"tokens": toks}, mode="train")
        cache = T.init_cache(r, 1, 32)
        lg, cache, _ = T.forward(r, PCFG, params, {"tokens": toks[:, :19]},
                                 mode="prefill", cache=cache,
                                 lengths=jnp.asarray([19], jnp.int32))
        lg2, _ = T.forward(r, PCFG, params, {"tokens": toks[:, 19:20]},
                           mode="decode", cache=cache,
                           write_pos=jnp.asarray(19),
                           lengths=jnp.asarray([20], jnp.int32))
        np.testing.assert_allclose(np.asarray(lg2[:, 0], np.float32),
                                   np.asarray(ref[:, 19], np.float32),
                                   rtol=3e-2, atol=3e-2)


class TestMoE:
    def test_shard_map_matches_dense_oracle(self, key):
        """EP dispatch on a 1x1 mesh (E_local == E) vs the dense path.

        With ample capacity and no drops the two must agree closely."""
        from repro.distributed.sharding import sharding_env
        from repro.launch.mesh import make_local_mesh
        r = reduced(ARCHS["llama4-scout-17b-a16e"])
        p = init_params(M.moe_defs(r), key)
        x = jax.random.normal(key, (2, 16, r.d_model), jnp.float32) \
            .astype(jnp.bfloat16)
        import dataclasses
        r_big_cap = dataclasses.replace(
            r, moe=dataclasses.replace(r.moe, capacity_factor=8.0))
        dense_out, dense_aux = M.moe_layer(
            r_big_cap, ParallelConfig(moe_impl="dense"), p, x)
        mesh = make_local_mesh(data=1, model=1)
        with sharding_env(mesh, fsdp=False):
            ep_out, ep_aux = M.moe_layer(
                r_big_cap, ParallelConfig(moe_impl="shard_map"), p, x)
        np.testing.assert_allclose(np.asarray(dense_out, np.float32),
                                   np.asarray(ep_out, np.float32),
                                   rtol=3e-2, atol=3e-2)
        np.testing.assert_allclose(float(dense_aux), float(ep_aux), rtol=1e-3)

    def test_capacity_drops_are_bounded(self, key):
        r = reduced(ARCHS["deepseek-v2-236b"])
        p = init_params(M.moe_defs(r), key)
        x = jax.random.normal(key, (2, 32, r.d_model)).astype(jnp.bfloat16)
        from repro.distributed.sharding import sharding_env
        from repro.launch.mesh import make_local_mesh
        with sharding_env(make_local_mesh(1, 1), fsdp=False):
            out, aux = M.moe_layer(r, ParallelConfig(moe_impl="shard_map"), p, x)
        assert bool(jnp.isfinite(out).all())
        assert float(aux) > 0.5  # load-balance loss in a sane range


@pytest.mark.parametrize("path,expect", [
    (("group0", "0_attn", "attn", "wq"), "d_model"),
    (("group0", "0_attn", "attn", "wk"), "d_model"),
    (("group0", "0_attn", "attn", "wo"), "heads*head_dim"),
    (("group0", "1_mlp", "mlp", "down"), "d_ff"),
])
def test_fan_in_is_the_summed_dims(path, expect):
    """The init scales each projection by the inputs it sums over: the
    model width for q/k/v (not the head count), heads x head_dim for the
    output projection — so attention scores start O(1), not hard-max."""
    from repro.models.params import fan_in, is_def
    cfg = ARCHS["granite-3-8b"]
    d = T.model_defs(cfg)
    for k in path:
        d = d[k]
    assert is_def(d)
    want = {"d_model": cfg.d_model, "d_ff": cfg.d_ff,
            "heads*head_dim": cfg.num_heads * cfg.resolved_head_dim}[expect]
    assert fan_in(d) == want
