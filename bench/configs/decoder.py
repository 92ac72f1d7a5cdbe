"""Plain reference of a decoder-only transformer with grouped-query attention.

Covers the configurations beside this file whose ``reference`` is
``decoder``: RMSNorm, q/k/v projections with optional bias, rotary
embeddings (the half-split rotation of the published models), causal
softmax attention where each of the ``num_key_value_heads`` groups serves
``num_attention_heads / num_key_value_heads`` query heads, and a SwiGLU
feed-forward, either dense or a mixture of experts that routes each token
to its ``num_experts_per_tok`` highest router logits and weighs them by a
softmax over those logits.

Written from the published description in float32 at the highest matmul
precision, one layer at a time over whole sequences; it takes its weights
from the benchmark's seeded generator and nothing from the program.
``quant="fp8"`` is the control, the same arithmetic stored in float8 (e4m3)
where the program stores bfloat16: every weight (one scale per output
channel), every activation entering or leaving a projection and the
residual stream after each add (one scale per token) are rounded to
float8; accumulation stays float32.

Beside the reference, the architecture's description on the benchmark's
side: the program's ``ModelConfig``, its parameter tree as leaf groups (one
stack of identical layers under ``stacked/0``), and the work each layer and
each attention kernel needs, counted from the published shapes.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import Group, layer_weights, top_weights

F32 = jnp.float32
Q_BLOCK = 512
BUCKET = 1024


# ---------------------------------------------------------------------------
# The architecture as the program runs it
# ---------------------------------------------------------------------------

def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


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


def _layer_leaves(c: dict):
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
    return tuple(out)


def layout(c: dict) -> Sequence[Group]:
    """The top leaves at the root, and every layer in one stack under
    ``stacked/0``, as the program scans a one-kind layer pattern."""
    d, V = c["hidden_size"], c["vocab_size"]
    top = [("embed", (V, d), "bfloat16", ("around", 0.0, math.sqrt(3.0))),
           ("final_norm.w", (d,), "float32", ("around", 1.0, 0.25))]
    if not c["tie_word_embeddings"]:
        top.append(("unembed", (d, V), "bfloat16", ("uniform", d)))
    return (Group("", tuple(top)),
            Group("stacked/0", _layer_leaves(c),
                  tuple(range(c["num_hidden_layers"]))))


# ---------------------------------------------------------------------------
# Work: operations and bytes from the published shapes (bench/work.py)
# ---------------------------------------------------------------------------

def _pairs(n: int, h: int) -> int:
    """Query-key pairs of n causal queries after h cached tokens."""
    return n * h + n * (n + 1) // 2


def attention_work(c: dict, segments: Iterable[Tuple[int, int]],
                   layer: int = 0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one attention kernel call over one layer: QK^T and
    PV for every causal pair; reads q and each row's valid K/V once,
    writes the output; bf16 operands.  Every layer alike."""
    H, G, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    flops = nbytes = 0.0
    for n, h in segments:
        flops += 4.0 * H * hd * _pairs(n, h)
        nbytes += 2.0 * (2 * n * H * hd + 2 * (h + n) * G * hd)
    return flops, nbytes


#: the decode kernel's call (one query per row) needs the same count: each
#: row's live K/V read once, its q read and its output written
decode_attention_work = attention_work


def layer_matmul_params(c: dict, layer: int = 0) -> int:
    """Weights one token multiplies by in one layer (top-k experts only);
    every layer alike."""
    d, H, G = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = head_dim(c), c["intermediate_size"]
    attn = d * (H + 2 * G) * hd + H * hd * d
    E = int(c.get("num_local_experts") or 0)
    if E:
        return attn + d * E + 3 * d * ff * int(c["num_experts_per_tok"])
    return attn + 3 * d * ff


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    m = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(m > 0, m / 448.0, 1.0)
    # keep the quotient inside e4m3's range: a cast beyond it may give NaN
    y = jnp.clip(x / s, -448.0, 448.0)
    return y.astype(jnp.float8_e4m3fn).astype(F32) * s


def _tok8(x, quant):
    """Round an activation (T, ...) to float8 with one scale per token."""
    if not quant:
        return x
    flat = x.reshape(x.shape[0], -1)
    return _q8(flat, -1).reshape(x.shape)


def _mm(x, w, quant):
    """x (T, din) @ w (din, ...) in float32."""
    w = w.astype(F32)
    if quant:
        x = _q8(x, -1)
        w = _q8(w, 0)
    return _tok8(jnp.tensordot(x, w, axes=1), quant)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, None].astype(F32) * inv                     # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, w, n_valid, *, dims, quant):
    H, G, hd, eps, theta, k = dims
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    h = _rms(x, w["norm.w"], eps)
    q = _mm(h, w["wq"], quant)                               # (T, H, hd)
    kk = _mm(h, w["wk"], quant)                              # (T, G, hd)
    v = _mm(h, w["wv"], quant)
    if "bq" in w:
        q, kk, v = (q + w["bq"].astype(F32), kk + w["bk"].astype(F32),
                    v + w["bv"].astype(F32))
    q, kk = _rope(q, pos, theta), _rope(kk, pos, theta)
    rep = H // G
    kh = jnp.repeat(kk, rep, axis=1)                         # (T, H, hd)
    vh = jnp.repeat(v, rep, axis=1)
    outs = []
    for lo in range(0, T, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = jnp.einsum("shd,thd->hst", qb, kh) * (hd ** -0.5)
        qpos = pos[lo:lo + Q_BLOCK]
        ok = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < n_valid)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hst,thd->shd", p, vh))
    att = jnp.concatenate(outs, 0)                           # (T, H, hd)
    x = _tok8(x + _mm(att.reshape(T, H * hd),
                      w["wo"].reshape(H * hd, -1), quant), quant)
    h = _rms(x, w["ffn_norm.w"], eps)
    if "moe.router" in w:
        logits = h @ w["moe.router"]                         # (T, E)
        top, ids = jax.lax.top_k(logits, k)
        gate = jax.nn.softmax(top, axis=-1)
        y = jnp.zeros_like(x)
        for e in range(logits.shape[-1]):
            g_e = jnp.sum(jnp.where(ids == e, gate, 0.0), -1)   # (T,)
            up = _mm(h, w["moe.wi"][e], quant)
            gt = _mm(h, w["moe.wg"][e], quant)
            y = y + g_e[:, None] * _mm(jax.nn.silu(gt) * up,
                                       w["moe.wo"][e], quant)
    else:
        up = _mm(h, w["mlp.wi"], quant)
        gt = _mm(h, w["mlp.wg"], quant)
        y = _mm(jax.nn.silu(gt) * up, w["mlp.wo"], quant)
    return _tok8(x + y, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, norm_w, unembed, eps, *, quant):
    """Logits in float32 (the program returns float32 logits too)."""
    h, w = _rms(h, norm_w, eps), unembed.astype(F32)
    if quant:
        h, w = _q8(h, -1), _q8(w, 0)
    return h @ w


def logits_at(c: dict, seed: int, seqs: List[np.ndarray],
              positions: List[np.ndarray],
              quant: Optional[str] = None) -> List[jax.Array]:
    """Next-token logits (n, V) float32 at ``positions`` of each sequence."""
    q = quant == "fp8"
    dims = (c["num_attention_heads"], c["num_key_value_heads"], head_dim(c),
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            int(c.get("num_experts_per_tok") or 0))
    with jax.default_matmul_precision("highest"):
        top = top_weights(c, seed)
        xs, lens = [], []
        for toks in seqs:
            T = -(-len(toks) // BUCKET) * BUCKET
            ids = np.zeros(T, np.int32)
            ids[:len(toks)] = toks
            x = jnp.take(top["embed"], jnp.asarray(ids), 0).astype(F32)
            xs.append(_tok8(x, q))
            lens.append(len(toks))
        for layer in range(c["num_hidden_layers"]):
            w = layer_weights(c, seed, layer)
            xs = [_layer(x, w, n, dims=dims, quant=q) for x, n in zip(xs, lens)]
            del w
        unembed = top.get("unembed")
        if unembed is None:
            unembed = top["embed"].T
        return [_head(x[jnp.asarray(p)], top["final_norm.w"], unembed,
                      float(c["rms_norm_eps"]), quant=q)
                for x, p in zip(xs, positions)]
