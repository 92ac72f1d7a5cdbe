"""Test fixture architecture: a decoder whose layers alternate
sliding-window and full causal attention (``layer_types``), as the program
serves gemma2's block, with the plain SwiGLU layer otherwise.

The program scans such a model by pattern position: layers j, j + P, ...
stacked under ``stacked/<j>`` and the ``num_hidden_layers % P`` trailing
layers unstacked under ``rest/<i>``.  So its tree has two stacked groups
and an unstacked layer, a layout that ``bench/configs/decoder.py`` does
not describe; this module describes it, with its own work count and its
own float32 reference, and no file of the harness names it.
"""
from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Group, layer_weights, top_weights

F32 = jnp.float32
SLIDING = "sliding_attention"


def _period(c: dict) -> Tuple[str, ...]:
    """The shortest repeating prefix of ``layer_types``."""
    types = c["layer_types"]
    for p in range(1, len(types) + 1):
        if all(t == types[i % p] for i, t in enumerate(types)):
            return tuple(types[:p])
    raise ValueError(types)


def _window(c: dict, layer: int):
    return c["sliding_window"] if c["layer_types"][layer] == SLIDING else None


def model_config(c: dict):
    from repro.configs.base import ATTN, LOCAL, ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        layer_pattern=tuple(LOCAL if t == SLIDING else ATTN
                            for t in _period(c)),
        sliding_window=c["sliding_window"], rope_theta=float(c["rope_theta"]),
        activation="swiglu", tie_embeddings=bool(c["tie_word_embeddings"]),
        rms_eps=float(c["rms_norm_eps"]), dtype="bfloat16")


def layout(c: dict) -> Sequence[Group]:
    d, V, ff = c["hidden_size"], c["vocab_size"], c["intermediate_size"]
    H, G, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    layer = (("norm.w", (d,), "float32", ("around", 1.0, 0.25)),
             ("wq", (d, H, hd), "bfloat16", ("uniform", d)),
             ("wk", (d, G, hd), "bfloat16", ("uniform", d)),
             ("wv", (d, G, hd), "bfloat16", ("uniform", d)),
             ("wo", (H, hd, d), "bfloat16", ("uniform", H * hd)),
             ("ffn_norm.w", (d,), "float32", ("around", 1.0, 0.25)),
             ("mlp.wg", (d, ff), "bfloat16", ("uniform", d)),
             ("mlp.wi", (d, ff), "bfloat16", ("uniform", d)),
             ("mlp.wo", (ff, d), "bfloat16", ("uniform", ff)))
    top = (("embed", (V, d), "bfloat16", ("around", 0.0, math.sqrt(3.0))),
           ("final_norm.w", (d,), "float32", ("around", 1.0, 0.25)),
           ("unembed", (d, V), "bfloat16", ("uniform", d)))
    P, L = len(_period(c)), c["num_hidden_layers"]
    stacked = L // P * P
    return ((Group("", top),)
            + tuple(Group(f"stacked/{j}", layer, tuple(range(j, stacked, P)))
                    for j in range(P))
            + tuple(Group(f"rest/{i}", layer, (stacked + i,), stacked=False)
                    for i in range(L - stacked)))


def layer_matmul_params(c: dict, layer: int = 0) -> int:
    d, ff = c["hidden_size"], c["intermediate_size"]
    H, G, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    return d * (H + 2 * G) * hd + H * hd * d + 3 * d * ff


def attention_work(c: dict, segments, layer: int = 0):
    """(FLOPs, bytes) of one layer's attention call: on a sliding layer
    each query sees at most ``sliding_window`` keys, and only the keys some
    query sees are read."""
    H, G, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    W = _window(c, layer)
    flops = nbytes = 0.0
    for n, h in segments:
        seen = [h + i + 1 if W is None else min(W, h + i + 1) for i in range(n)]
        keys = h + n if W is None else min(h, W - 1) + n
        flops += 4.0 * H * hd * sum(seen)
        nbytes += 2.0 * (2 * n * H * hd + 2 * keys * G * hd)
    return flops, nbytes


# ---------------------------------------------------------------------------
# The plain reference, float32 at the highest matmul precision
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "window"))
def _layer(x, w, *, dims, window):
    H, G, hd, eps, theta = dims
    T = x.shape[0]
    pos = jnp.arange(T)
    f = {k: v.astype(F32) for k, v in w.items()}
    h = _rms(x, f["norm.w"], eps)
    q = _rope(jnp.einsum("td,dhp->thp", h, f["wq"]), pos, theta)
    k = _rope(jnp.einsum("td,dgp->tgp", h, f["wk"]), pos, theta)
    v = jnp.einsum("td,dgp->tgp", h, f["wv"])
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    s = jnp.einsum("shp,thp->hst", q, k) * hd ** -0.5
    ok = pos[None, :] <= pos[:, None]
    if window is not None:
        ok &= (pos[:, None] - pos[None, :]) < window
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    x = x + jnp.einsum("hst,thp,hpd->sd", p, v, f["wo"])
    h = _rms(x, f["ffn_norm.w"], eps)
    y = jax.nn.silu(h @ f["mlp.wg"]) * (h @ f["mlp.wi"])
    return x + y @ f["mlp.wo"]


def logits_at(c: dict, seed: int, seqs: List[np.ndarray],
              positions: List[np.ndarray]) -> List[jax.Array]:
    """Next-token logits (n, V) float32 at ``positions`` of each sequence."""
    dims = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
            float(c["rms_norm_eps"]), float(c["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        top = top_weights(c, seed)
        xs = [jnp.take(top["embed"], jnp.asarray(t), 0).astype(F32)
              for t in seqs]
        for layer in range(c["num_hidden_layers"]):
            w = layer_weights(c, seed, layer)
            xs = [_layer(x, w, dims=dims, window=_window(c, layer))
                  for x in xs]
        out = []
        for x, p in zip(xs, positions):
            h = _rms(x[jnp.asarray(p)], top["final_norm.w"],
                     float(c["rms_norm_eps"]))
            out.append(h @ top["unembed"].astype(F32))
        return out
