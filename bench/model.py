"""A configuration file as the program runs it, and its weights from a seed.

The configuration file holds the model's published ``config.json`` keys (with
depth cut as its ``published`` entry records) plus a ``serving`` group.
``model_config`` turns it into the program's ``ModelConfig``; nothing here
reads the program's own configuration registry.

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
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file's keys."""
    from repro.configs.base import ATTN, ModelConfig

    experts = int(c.get("num_local_experts") or 0)
    return ModelConfig(
        name=c["name"],
        family="moe" if experts else "dense",
        num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=head_dim(c),
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        layer_pattern=(ATTN,),
        qkv_bias=bool(c.get("attention_bias", False)),
        rope_theta=float(c["rope_theta"]),
        activation="swiglu",
        tie_embeddings=bool(c["tie_word_embeddings"]),
        num_experts=experts,
        num_experts_per_tok=int(c.get("num_experts_per_tok") or 0),
        rms_eps=float(c["rms_norm_eps"]),
        dtype="bfloat16",
    )


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


# ---------------------------------------------------------------------------
# Leaves: (name, per-layer shape, dtype, init) in the program's tree layout
# ---------------------------------------------------------------------------

def layer_leaves(c: dict) -> List[Tuple[str, Tuple[int, ...], str, Tuple]]:
    """Per-layer leaves; ``init`` is ("uniform", fan_in) or ("around", centre,
    half-width)."""
    d, H, G = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = head_dim(c), c["intermediate_size"]
    out = [
        ("norm.w", (d,), "float32", ("around", 1.0, 0.25)),
        ("wq", (d, H, hd), "bfloat16", ("uniform", d)),
        ("wk", (d, G, hd), "bfloat16", ("uniform", d)),
        ("wv", (d, G, hd), "bfloat16", ("uniform", d)),
        ("wo", (H, hd, d), "bfloat16", ("uniform", H * hd)),
        ("ffn_norm.w", (d,), "float32", ("around", 1.0, 0.25)),
    ]
    if c.get("attention_bias"):
        out += [("bq", (H, hd), "bfloat16", ("around", 0.0, 0.5)),
                ("bk", (G, hd), "bfloat16", ("around", 0.0, 0.5)),
                ("bv", (G, hd), "bfloat16", ("around", 0.0, 0.5))]
    E = int(c.get("num_local_experts") or 0)
    if E:
        out += [("moe.router", (d, E), "float32", ("uniform", d)),
                ("moe.wg", (E, d, ff), "bfloat16", ("uniform", d)),
                ("moe.wi", (E, d, ff), "bfloat16", ("uniform", d)),
                ("moe.wo", (E, ff, d), "bfloat16", ("uniform", ff))]
    else:
        out += [("mlp.wg", (d, ff), "bfloat16", ("uniform", d)),
                ("mlp.wi", (d, ff), "bfloat16", ("uniform", d)),
                ("mlp.wo", (ff, d), "bfloat16", ("uniform", ff))]
    return out


def top_leaves(c: dict) -> List[Tuple[str, Tuple[int, ...], str, Tuple]]:
    d, V = c["hidden_size"], c["vocab_size"]
    out = [("embed", (V, d), "bfloat16", ("around", 0.0, math.sqrt(3.0))),
           ("final_norm.w", (d,), "float32", ("around", 1.0, 0.25))]
    if not c["tie_word_embeddings"]:
        out.append(("unembed", (d, V), "bfloat16", ("uniform", d)))
    return out


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


def layer_weights(c: dict, seed: int, layer: int) -> Dict[str, jax.Array]:
    """One layer's leaves by flat name, regenerated from the seed."""
    return {n: _leaf(jnp.asarray(leaf_key(seed, n, layer)), shape=s,
                     dtype=dt, init=init)
            for n, s, dt, init in layer_leaves(c)}


def top_weights(c: dict, seed: int) -> Dict[str, jax.Array]:
    return {n: _leaf(jnp.asarray(leaf_key(seed, n, 0)), shape=s, dtype=dt,
                     init=init)
            for n, s, dt, init in top_leaves(c)}


def _nest(flat: Dict[str, jax.Array]) -> dict:
    out: dict = {}
    for name, v in flat.items():
        node = out
        parts = name.split(".")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def make_params(c: dict, seed: int, device=None) -> dict:
    """The program's parameter tree for this configuration, made on the
    device in one jitted call: layers stacked on a leading axis as the
    program scans them."""
    L = c["num_hidden_layers"]
    lleaves, tleaves = layer_leaves(c), top_leaves(c)
    keys = {n: np.stack([leaf_key(seed, n, i) for i in range(L)])
            for n, *_ in lleaves}
    keys.update({n: leaf_key(seed, n, 0) for n, *_ in tleaves})

    def build(keys):
        stacked = {n: jax.vmap(lambda k, s=s, dt=dt, init=init:
                               leaf_values(k, s, dt, init))(keys[n])
                   for n, s, dt, init in lleaves}
        tree = _nest({n: leaf_values(keys[n], s, dt, init)
                      for n, s, dt, init in tleaves})
        tree["stacked"] = {"0": _nest(stacked)}
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
