"""The benchmark's weights: the program's tree layout, and bit-identical
regeneration of any layer from the seed (what the reference relies on)."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import json  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness, model  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TINY = ["tiny-decoder", "tiny-moe", "tiny-windowed"]


def _cfg(name):
    return harness.load_config(name, (FIX, harness.BENCH_DIR))


@pytest.mark.parametrize("name", TINY)
def test_tree_matches_the_program_layout(name):
    from repro.models.transformer import init_params
    c = _cfg(name)
    ours = jax.eval_shape(lambda: model.make_params(c, 3))
    theirs = jax.eval_shape(lambda: init_params(model.model_config(c),
                                                jax.random.PRNGKey(0)))
    assert (jax.tree.structure(ours) == jax.tree.structure(theirs))
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def _at(tree, path, name):
    node = tree
    for part in [p for p in path.split("/") if p] + name.split("."):
        node = node[part]
    return node


@pytest.mark.parametrize("name", TINY)
def test_layers_regenerate_bit_for_bit(name):
    """Every layer's leaves, wherever its group puts them (stacked at the
    layer's place in the group, or unstacked), and the top leaves."""
    c = _cfg(name)
    seed = 2 ** 40 + 17
    tree = model.make_params(c, seed)
    where = {}
    for g in model.layout(c):
        for i, layer in enumerate(g.layers or ()):
            where[layer] = (g.path, i if g.stacked else None)
    assert sorted(where) == list(range(c["num_hidden_layers"]))
    for layer, (path, i) in where.items():
        for n, v in model.layer_weights(c, seed, layer).items():
            got = _at(tree, path, n)
            np.testing.assert_array_equal(
                np.asarray(got if i is None else got[i]), np.asarray(v))
    for n, v in model.top_weights(c, seed).items():
        np.testing.assert_array_equal(np.asarray(_at(tree, "", n)),
                                      np.asarray(v))


def test_seeds_give_different_weights():
    c = _cfg("tiny-decoder")
    a = model.layer_weights(c, 1, 0)["wq"]
    b = model.layer_weights(c, 2, 0)["wq"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", sorted(CONFIGS.glob("*.json")))
def test_benchmark_configs_build_a_model_config(path):
    c = json.loads(path.read_text())
    c["name"] = path.stem
    cfg = model.model_config(c)
    assert cfg.d_model == c["hidden_size"]
    assert cfg.resolved_head_dim * cfg.num_heads == c["hidden_size"]
