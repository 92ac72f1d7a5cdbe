"""Pallas TPU flash attention for (incremental) prefill.

The operator AMPD schedules: queries for an ``l_incr`` chunk attend over
``l_hist`` cached tokens plus the chunk's own causal prefix.  Position-based
masking (q/kv position vectors) subsumes initial prefill (hist = 0), chunked
incremental prefill, sliding windows (gemma2/recurrentgemma local layers) and
single-token decode (S = #q-rows with equal positions).

TPU mapping (DESIGN.md §6):
  grid = (batch, q_heads, q_blocks, kv_blocks); the last (kv) grid dim is
  sequential ("arbitrary"), carrying the online-softmax accumulators
  (acc/m/l) in VMEM scratch across iterations.  Block shapes are MXU-aligned
  (block_q x head_dim, block_kv x head_dim; head_dim pre-padded to a lane
  multiple of 128 by ops.py).  GQA is handled in the k/v index_map
  (h -> h // q_per_group), so KV blocks stay in VMEM across the q-head
  revisits of the same group.

Numerics: logits/softmax in fp32, optional tanh softcap, big-negative mask
fill; fully-masked rows produce zeros (l clamped), matching ref.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
INVALID_POS = -(2 ** 30)
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref,   # inputs
                  o_ref,                                     # outputs
                  acc_ref, m_ref, l_ref,                     # scratch
                  *, scale: float, softcap: Optional[float],
                  window: Optional[int], causal: bool, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)                      # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qp = qpos_ref[0]                                         # (bq, 1)
    kp = kpos_ref[0]                                         # (1, bkv)
    mask = kp > (INVALID_POS // 2)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...][:, 0]                                # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)                              # exp(NEG-NEG)=1 guard
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


def flash_prefill_bhsd(
    q: jax.Array,                    # (B, H, S, hd)  hd % 128 == 0
    k: jax.Array,                    # (B, G, T, hd)
    v: jax.Array,
    q_positions: jax.Array,          # (B, S) int32
    kv_positions: jax.Array,         # (B, T) int32
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, hd = q.shape
    G, T = k.shape[1], k.shape[2]
    assert H % G == 0 and S % block_q == 0 and T % block_kv == 0, (H, G, S, T)
    qpg = H // G
    nq, nk = S // block_q, T // block_kv

    kernel = functools.partial(
        _flash_kernel, scale=scale, softcap=softcap, window=window,
        causal=causal, nk=nk)

    # positions as (B, S, 1) / (B, 1, T): every block's last two dims are
    # then sublane/lane aligned or whole, as Mosaic requires at any B
    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda b, h, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda b, h, qi, ki: (b, 0, ki)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, qi, ki, _qpg=qpg: (b, h // _qpg, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda b, h, qi, ki, _qpg=qpg: (b, h // _qpg, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_positions[:, :, None], kv_positions[:, None, :], q, k, v)
    return out
