"""Pallas TPU decode attention: one new token per sequence vs a long KV ring.

Memory-bound by design (the phase PD disaggregation gives its own workers).
Layout: queries are the ``q_per_group`` heads of one GQA group, processed as
the row dim of an MXU tile — grid (batch, kv_heads, kv_blocks); the kv grid
dim is sequential and carries online-softmax state in VMEM scratch.

``return_residuals=True`` additionally emits per-row (m, l) so a *sequence-
sharded* KV cache (context-parallel decode, DESIGN.md §5 — the beyond-paper
optimization) can run this same kernel per shard and combine partials with
two tiny collectives:  m* = max_i m_i;  l* = sum_i l_i e^{m_i-m*};
o* = sum_i o_i l_i e^{m_i-m*} / l*.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
INVALID_POS = -(2 ** 30)
DEFAULT_BLOCK_KV = 512


def _decode_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,
                   o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref,
                   *, scale: float, softcap: Optional[float],
                   window: Optional[int], nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                      # (rows, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qp = qpos_ref[0]                                         # (rows, 1)
    kp = kpos_ref[0]                                         # (1, bkv)
    mask = (kp > (INVALID_POS // 2)) & (kp <= qp)
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[...][:, 0] + jnp.sum(p, axis=-1)

    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(ki == nk - 1)
    def _done():
        l = l_ref[...][:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        m_out_ref[0, 0] = m_ref[...]
        l_out_ref[0, 0] = l_ref[...]


def decode_attn_bgrd(
    q: jax.Array,                    # (B, G, rows, hd) rows = padded q_per_group
    k: jax.Array,                    # (B, G, T, hd)
    v: jax.Array,
    q_positions: jax.Array,          # (B, rows) int32 (same position, padded rows INVALID)
    kv_positions: jax.Array,         # (B, T) int32
    *,
    scale: float,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, G, rows, hd = q.shape
    T = k.shape[2]
    assert T % block_kv == 0, (T, block_kv)
    nk = T // block_kv

    kernel = functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                               window=window, nk=nk)
    # positions as (B, rows, 1) / (B, 1, T) so every block's last two dims
    # are sublane/lane aligned or whole, as Mosaic requires at any B
    out, m, l = pl.pallas_call(
        kernel,
        grid=(B, G, nk),
        in_specs=[
            pl.BlockSpec((1, rows, 1), lambda b, g, ki: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, g, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, rows, hd), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, g, ki: (b, g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, g, ki: (b, g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rows, hd), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1), lambda b, g, ki: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, rows, 1), lambda b, g, ki: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G, rows, hd), q.dtype),
            jax.ShapeDtypeStruct((B, G, rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, G, rows, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_positions[:, :, None], kv_positions[:, None, :], q, k, v)
    return out, m[..., 0], l[..., 0]


def combine_partials(o: jax.Array, m: jax.Array, l: jax.Array,
                     axis_name: str) -> jax.Array:
    """Flash-decoding combine across a sequence-sharded KV axis.

    o: (..., hd) normalized partial outputs; m, l: (...,) softmax stats.
    Runs inside shard_map; two psums + one pmax.
    """
    m_star = jax.lax.pmax(m, axis_name)
    w = l * jnp.exp(m - m_star)
    denom = jax.lax.psum(w, axis_name)
    num = jax.lax.psum(o.astype(jnp.float32) * w[..., None], axis_name)
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    return (num / denom_safe[..., None]).astype(o.dtype)
