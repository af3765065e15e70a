import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.core import weights
from bench.reference import dense_gqa
from bench.tests import tiny


def test_layer_draw_matches_stacked_slice():
    words = weights.seed_words(2 ** 40 + 3)
    shape = (3, 8, 4, 16)
    std = weights.leaf_std("group0/0_attn/attn/wq", shape, 8)
    whole = weights.draw_leaf(words, "group0/0_attn/attn/wq", shape, std,
                              stacked=True)
    for layer in range(3):
        one = weights.draw_leaf(words, "group0/0_attn/attn/wq", shape[1:],
                                std, stacked=True, layer=layer)
        np.testing.assert_array_equal(np.asarray(whole[layer]),
                                      np.asarray(one))


def test_seed_high_word_matters_and_std_holds():
    a = weights.draw_leaf(weights.seed_words(5), "embed/tok", (512, 64),
                          0.02, stacked=False)
    b = weights.draw_leaf(weights.seed_words(5 + 2 ** 32), "embed/tok",
                          (512, 64), 0.02, stacked=False)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert abs(float(jnp.std(a.astype(jnp.float32))) - 0.02) < 0.001


def test_reference_paths_cover_the_program_tree():
    from repro.models import transformer as T
    cfg = tiny.CFG
    table = weights.leaf_table(T.model_defs(dense_gqa.model_config(cfg)))
    mine = dense_gqa.layer_shapes(cfg)
    L = cfg["num_hidden_layers"]
    for path, shape in mine.items():
        assert table[path] == ((L,) + shape, True)
    assert set(table) - set(mine) == {"embed/tok", "final_norm"}


@pytest.mark.parametrize("tie", [True, False])
def test_token_flops_tie_to_param_count(tie):
    cfg = dict(tiny.CFG, tie_word_embeddings=tie)
    mcfg = dense_gqa.model_config(cfg)
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    # matmul weights = all parameters but the norms and the token lookup
    matmul = mcfg.param_count() - 2 * d * L - (0 if tie else V * d)
    assert (dense_gqa.token_flops(cfg) + dense_gqa.logits_flops(cfg)
            == 2 * matmul)


def test_prefill_and_decode_flops_add_up():
    cfg = tiny.CFG
    P, cached = 100, 48
    whole = dense_gqa.prefill_flops(cfg, P, 0)
    part = dense_gqa.prefill_flops(cfg, P, cached)
    per_pos = sum(dense_gqa.token_flops(cfg)
                  + dense_gqa.attention_flops(cfg, p + 1)
                  for p in range(cached))
    assert whole - part == pytest.approx(per_pos)
    assert dense_gqa.decode_flops(cfg, P + 1) == pytest.approx(
        dense_gqa.prefill_flops(cfg, P + 1, P))
    # a copy reads and writes a page in each arena, an init writes one
    pb = dense_gqa.page_bytes(cfg, 16)
    assert dense_gqa.page_op_bytes(cfg, 16, 1, 0) == 2 * pb
    assert dense_gqa.page_op_bytes(cfg, 16, 0, 1) == 4 * pb


def test_dense_draw_reference_and_counts_are_as_before():
    """The tiny dense tree's draw, its reference logits and its counts
    read what they read before the dense module took them over (recorded
    at that commit): the granite cell draws the same bits, runs the same
    reference and counts the same work.  The logits are compared within
    float32 rounding, which the CPU's thread count moves."""
    from repro.models import transformer as T
    cfg, seed = tiny.CFG, 2 ** 35 + 17
    tree = weights.draw_tree(T.model_defs(dense_gqa.model_config(cfg)),
                             seed, cfg["hidden_size"], dense_gqa.leaf_std)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == ("8e04c14a92b2fa2b2d240e004c6168ab"
                             "497f876114dd1923f5ebe4dce0a49c1f")
    V = cfg["vocab_size"]
    seqs = [(np.arange(40, dtype=np.int32) * 7 + 3) % V,
            (np.arange(70, dtype=np.int32) * 13 + 1) % V]
    lg = dense_gqa.logits_at(cfg, seed, seqs,
                             [np.arange(30, 40), np.arange(50, 70)])
    np.testing.assert_allclose(
        [np.abs(np.asarray(x, np.float64)).sum() for x in lg],
        [658.2440185937448, 1284.1679198470738], rtol=1e-6)
    np.testing.assert_allclose(
        [np.asarray(x, np.float64).sum() for x in lg],
        [7.805777617511922, 62.895819886885874], atol=1e-4)
    np.testing.assert_allclose(
        [x[0, :4] for x in lg],
        [[-0.1611369401216507, 0.028507474809885025, 0.137362539768219,
          0.2672649025917053],
         [0.17706803977489471, 0.029797866940498352, 0.07217275351285934,
          0.0616423599421978]], atol=1e-6)
    assert dense_gqa.prefill_flops(cfg, 100, 0) == 17396736.0
    assert dense_gqa.prefill_flops(cfg, 100, 48) == 9716736.0
    assert dense_gqa.decode_flops(cfg, 101) == 264704.0
    assert dense_gqa.page_op_bytes(cfg, 16, 3, 2) == 28672
