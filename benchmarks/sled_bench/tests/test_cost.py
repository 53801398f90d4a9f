"""Needed work per verify round, checked against the program's own
parameter tree and a brute-force count at tiny sizes."""
import json

import jax
import numpy as np
import pytest

from helpers import HERE
import cost

TINY = json.loads((HERE / "data" / "tiny-dense.json").read_text())


def program_tree(config):
    from adapters import dense_transformer as adapter

    model = adapter.model(dict(config, name="t"))
    return model, jax.eval_shape(model.init_params, jax.random.key(0))


@pytest.mark.parametrize("tied", [True, False])
def test_weight_bytes_and_params_match_the_program(tied):
    config = dict(TINY, tie_word_embeddings=tied)
    sh = cost.Shapes.from_config(config)
    _, tree = program_tree(config)
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    total = sum(int(np.prod(a.shape)) * a.dtype.itemsize for _, a in leaves)
    embed = tree["embed"]
    gathered_only = 0 if tied else int(np.prod(embed.shape)) * embed.dtype.itemsize
    assert sh.weight_bytes == total - gathered_only
    layer_mats = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree["layers"]) if a.ndim == 3)
    assert sh.matmul_params == layer_mats + sh.d * sh.V


def test_kv_bytes_per_position_match_the_pool():
    from repro.models.kvcache import PagedKVCache

    model, _ = program_tree(TINY)
    pool = PagedKVCache(model, 3, 64, attn_chunk=32)
    sh = cost.Shapes.from_config(TINY)
    per_row = pool.bytes_per_slot() - 4  # the row's length counter
    assert per_row == sh.kv_bytes_per_position * 64


def test_attention_pairs_brute_force():
    sh = cost.Shapes.from_config(TINY)
    for live, pos in [(0, 1), (5, 5), (100, 3)]:
        pairs = sum(live + j + 1 for j in range(pos))
        f, b = cost.request_work(sh, live, pos)
        assert f == 2.0 * sh.matmul_params * pos + 4.0 * sh.hq * sh.hd * sh.L * pairs
        assert b == sh.kv_bytes_per_position * (live + pos) + pos * sh.d * 2


def test_round_reads_weights_once():
    sh = cost.Shapes.from_config(TINY)
    f1, b1 = cost.round_work(sh, [(10, 5)])
    f2, b2 = cost.round_work(sh, [(10, 5), (10, 5)])
    assert f2 == 2 * f1
    assert b2 - b1 == b1 - sh.weight_bytes


def test_unknown_chip_is_an_error():
    assert cost.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cost.peak("TPU v9 imaginary")
