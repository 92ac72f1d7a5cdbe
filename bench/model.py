"""A configuration file as the program runs it, and its weights from a seed.

The configuration file holds the model's published ``config.json`` keys (with
depth cut as its ``published`` entry records), a ``serving`` group, and under
``reference`` the name of its architecture module, ``configs/<reference>.py``
beside the file.  That module is the architecture's whole description on the
benchmark's side: the program's ``ModelConfig`` (``model_config``), the
program's parameter tree as groups of leaves (:class:`Group`) (``layout``), the
work each layer and kernel needs (``bench.work``), and the plain reference
(``logits_at``).  Nothing here names an architecture's keys.

Weights are the benchmark's input, not the program's: every leaf is hashed
from ``(seed, leaf name, layer)`` and the element index by
:func:`leaf_values`, so the plain
reference can regenerate any one layer bit for bit without ever seeing an
array the program held.  :func:`make_params` builds the whole tree in the
program's layout in one jitted call on the device.
"""
from __future__ import annotations

import functools
import hashlib
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: one leaf: (flat name, per-layer shape, dtype, init), where ``init`` is
#: ("uniform", fan_in) or ("around", centre, half-width)
Leaf = Tuple[str, Tuple[int, ...], str, Tuple]


@dataclass(frozen=True)
class Group:
    """Leaves at one place of the program's parameter tree.

    ``path`` is the place ('/'-separated, "" for the root).  ``layers``
    None: the model's top leaves (embedding, final norm, head), keyed as
    layer 0.  Otherwise the layers whose copies of ``leaves`` sit there:
    stacked on a leading axis in that order, or, with ``stacked`` False,
    the one layer held unstacked.
    """
    path: str
    leaves: Tuple[Leaf, ...]
    layers: Optional[Tuple[int, ...]] = None
    stacked: bool = True


@functools.lru_cache(maxsize=None)
def _load_architecture(path: str):
    from bench.harness import load_module
    return load_module(Path(path))


def architecture(c: dict, search: Optional[Sequence] = None):
    """The module a configuration names under ``reference``: the one that
    ``harness.load_config`` found beside the configuration file, else
    ``configs/<reference>.py`` in the directories of ``search`` (the
    benchmark's own by default)."""
    path = c.get("architecture")
    if path is None:
        from bench.harness import BENCH_DIR, find
        path = find("configs", c["reference"], ".py", search or (BENCH_DIR,))
    return _load_architecture(str(path))


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file's keys."""
    return architecture(c).model_config(c)


def layout(c: dict) -> Sequence[Group]:
    return architecture(c).layout(c)


def leaf_key(seed: int, name: str, layer: int) -> np.ndarray:
    """Two 32-bit words naming one leaf of one layer under one seed."""
    h = hashlib.blake2b(f"{int(seed)}/{name}/{layer}".encode(),
                        digest_size=8).digest()
    return np.frombuffer(h, np.uint32).copy()


def _fmix(x):
    """The murmur3 finaliser: an avalanche over uint32 lanes."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def leaf_values(key, shape, dtype: str, init) -> jax.Array:
    """Uniform values hashed from (key, element index); every step is exact
    integer arithmetic or one float rounding, so the same key gives the
    same bits in any program, fused or not."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    bits = _fmix(_fmix(idx * jnp.uint32(0x9E3779B1) + key[0]) ^ key[1])
    u = (bits >> 9).astype(F32) * F32(2.0 ** -23)          # [0, 1), exact
    sym = u * F32(2.0) - F32(1.0)                           # [-1, 1), exact
    if init[0] == "uniform":
        half = math.sqrt(3.0 / init[1])                     # std 1/sqrt(fan_in)
        x = sym * F32(half)
    else:
        x = F32(init[1]) + sym * F32(init[2])
    return x.astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "init"))
def _leaf(key, *, shape, dtype, init):
    return leaf_values(key, shape, dtype, init)


def _values(seed: int, leaves, layer: int) -> Dict[str, jax.Array]:
    return {n: _leaf(jnp.asarray(leaf_key(seed, n, layer)), shape=s,
                     dtype=dt, init=init)
            for n, s, dt, init in leaves}


def layer_weights(c: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves by flat name, regenerated from the seed."""
    out: Dict[str, jax.Array] = {}
    for g in layout(c):
        if g.layers is not None and layer in g.layers:
            out.update(_values(seed, g.leaves, layer))
    return out


def top_weights(c: dict, seed: int) -> Dict[str, jax.Array]:
    out: Dict[str, jax.Array] = {}
    for g in layout(c):
        if g.layers is None:
            out.update(_values(seed, g.leaves, 0))
    return out


def _place(tree: dict, path: str, flat: Dict[str, jax.Array]) -> None:
    """Put ``flat`` (dotted leaf names) under ``path`` of ``tree``."""
    for name, v in flat.items():
        node = tree
        parts = [p for p in path.split("/") if p] + name.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v


def make_params(c: dict, seed: int, device=None) -> dict:
    """The program's parameter tree for this configuration, made on the
    device in one jitted call: each group's layers stacked on a leading
    axis where the program scans them."""
    groups = layout(c)
    keys = []
    for g in groups:
        if g.layers is None or not g.stacked:
            at = 0 if g.layers is None else g.layers[0]
            keys.append({n: leaf_key(seed, n, at) for n, *_ in g.leaves})
        else:
            keys.append({n: np.stack([leaf_key(seed, n, i) for i in g.layers])
                         for n, *_ in g.leaves})

    def build(keys):
        tree: dict = {}
        for g, k in zip(groups, keys):
            if g.layers is None or not g.stacked:
                flat = {n: leaf_values(k[n], s, dt, init)
                        for n, s, dt, init in g.leaves}
            else:
                flat = {n: jax.vmap(lambda x, s=s, dt=dt, init=init:
                                    leaf_values(x, s, dt, init))(k[n])
                        for n, s, dt, init in g.leaves}
            _place(tree, g.path, flat)
        return tree

    with jax.default_device(device):
        return jax.jit(build)(keys)


def engine_with_params(engine_cls, params) -> Callable:
    """An ``Engine`` constructor that serves ``params`` wherever the caller
    passes none (the cluster builds its first engine without weights)."""
    def make(*args, params_=params, **kw):
        if kw.get("params") is None:
            kw["params"] = params_
        kw.pop("key", None)
        return engine_cls(*args, **kw)
    return make
