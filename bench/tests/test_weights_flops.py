import jax.numpy as jnp
import numpy as np
import pytest

from bench.core import flops, weights
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
    from bench.run import model_config
    cfg = tiny.CFG
    table = weights.leaf_table(T.model_defs(model_config(cfg)))
    mine = dense_gqa.layer_shapes(cfg)
    L = cfg["num_hidden_layers"]
    for path, shape in mine.items():
        assert table[path] == ((L,) + shape, True)
    assert set(table) - set(mine) == {"embed/tok", "final_norm"}


@pytest.mark.parametrize("tie", [True, False])
def test_token_flops_tie_to_param_count(tie):
    from bench.run import model_config
    cfg = dict(tiny.CFG, tie_word_embeddings=tie)
    mcfg = model_config(cfg)
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    # matmul weights = all parameters but the norms and the token lookup
    matmul = mcfg.param_count() - 2 * d * L - (0 if tie else V * d)
    assert flops.token_flops(cfg) + flops.logits_flops(cfg) == 2 * matmul


def test_prefill_and_decode_flops_add_up():
    cfg = tiny.CFG
    P, cached = 100, 48
    whole = flops.prefill_flops(cfg, P, 0)
    part = flops.prefill_flops(cfg, P, cached)
    per_pos = sum(flops.token_flops(cfg) + flops.attention_flops(cfg, p + 1)
                  for p in range(cached))
    assert whole - part == pytest.approx(per_pos)
    assert flops.decode_flops(cfg, P + 1) == pytest.approx(
        flops.prefill_flops(cfg, P + 1, P))
    # a copy reads and writes a page in each arena, an init writes one
    pb = flops.page_bytes(cfg, 16)
    assert flops.page_op_bytes(cfg, 16, 1, 0) == 2 * pb
    assert flops.page_op_bytes(cfg, 16, 0, 1) == 4 * pb
