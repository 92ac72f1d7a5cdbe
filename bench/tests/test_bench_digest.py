"""The benchmark's weights stay bit for bit what they were before the
architecture modules described them: the digests below were taken from
the weights of the layout that ``bench/model.py`` spelled out itself
(the GQA / Mixtral leaves in one stack), with this file's functions."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import hashlib  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import harness, model  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures"
SEED = 2 ** 33 + 7
#: leaves of at most this many elements are hashed at the published widths
SMALL = 2 ** 20

PUBLISHED = {
    "qwen2.5-14b": (
        "45a47012d4e74bd4ad269cc6cfa7890704a5dc193c46278174a1b21d46c3996f",
        "e8e908fdc1d704bd4505c91b6c9c467c2a5681649222db60b43e96fc5af8c2f2",
        41),
    "mixtral-8x7b": (
        "620db64e325b534998ca07a7644c5ee5e18932393de175c6fe35f6af59c80389",
        "e053c0d99b23d20f9a1331ffeb01b793d69824b59c09dbf10296330c68410e6c",
        10),
}
TINY = {
    "tiny-decoder": (
        "30d3d51e3613148ec26c067a7300055d64427580c120524447f381f4b1d163aa",
        "e54500fd70e66d239e710e05f28ba74f14fb4ae6f9f5b71619c584b59e54575b"),
    "tiny-moe": (
        "cc73a494cde58ce3ef1ce3dcf21b832be57ef1ca1141f19943174f57515da87f",
        "f74709807e39dcb43fc533baea42dd8ad0ea14d84e36d337933d6c71c3e2b48e"),
}


def tree_digest(c) -> str:
    """Every leaf's place, shape and dtype, without making a weight."""
    t = jax.eval_shape(lambda: model.make_params(c, SEED))
    lines = [f"{jax.tree_util.keystr(p)} {x.shape} {x.dtype}"
             for p, x in jax.tree_util.tree_flatten_with_path(t)[0]]
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


def small_digest(c):
    """The bits of every small leaf of every layer (norms, biases, the
    router) and of the small top leaves, each from its (seed, name,
    layer) key."""
    items = []
    for g in model.layout(c):
        for n, s, dt, init in g.leaves:
            if np.prod(s) > SMALL:
                continue
            for layer in g.layers or (None,):
                label = f"{n}/{'top' if layer is None else layer}"
                items.append((label, n, layer or 0, s, dt, init))
    h = hashlib.sha256()
    for label, n, layer, s, dt, init in sorted(items):
        v = model.leaf_values(jnp.asarray(model.leaf_key(SEED, n, layer)),
                              s, dt, init)
        h.update(label.encode())
        h.update(np.asarray(v).tobytes())
    return h.hexdigest(), len(items)


def all_digest(c) -> str:
    h = hashlib.sha256()
    t = model.make_params(c, SEED)
    for p, x in jax.tree_util.tree_flatten_with_path(t)[0]:
        h.update(jax.tree_util.keystr(p).encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_weights_are_unchanged(name):
    c = harness.load_config(name, (harness.BENCH_DIR,))
    tree, small, n_small = PUBLISHED[name]
    assert tree_digest(c) == tree
    assert small_digest(c) == (small, n_small)


@pytest.mark.parametrize("name", sorted(TINY))
def test_fixture_weights_are_unchanged(name):
    c = harness.load_config(name, (FIX, harness.BENCH_DIR))
    tree, every = TINY[name]
    assert tree_digest(c) == tree
    assert all_digest(c) == every
