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

from bench import model  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures" / "configs"
CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _cfg(name):
    c = json.loads((FIX / f"{name}.json").read_text())
    c["name"] = name
    return c


@pytest.mark.parametrize("name", ["tiny-decoder", "tiny-moe"])
def test_tree_matches_the_program_layout(name):
    from repro.models.transformer import init_params
    c = _cfg(name)
    ours = jax.eval_shape(lambda: model.make_params(c, 3))
    theirs = jax.eval_shape(lambda: init_params(model.model_config(c),
                                                jax.random.PRNGKey(0)))
    assert (jax.tree.structure(ours) == jax.tree.structure(theirs))
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("name", ["tiny-decoder", "tiny-moe"])
def test_layers_regenerate_bit_for_bit(name):
    c = _cfg(name)
    seed = 2 ** 40 + 17
    tree = model.make_params(c, seed)
    stacked = tree["stacked"]["0"]
    for layer in range(c["num_hidden_layers"]):
        for n, v in model.layer_weights(c, seed, layer).items():
            node = stacked
            for part in n.split("."):
                node = node[part]
            np.testing.assert_array_equal(np.asarray(node[layer]),
                                          np.asarray(v))
    for n, v in model.top_weights(c, seed).items():
        node = tree
        for part in n.split("."):
            node = node[part]
        np.testing.assert_array_equal(np.asarray(node), np.asarray(v))


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
