"""Pallas TPU megakernel: ragged fused chunk+decode attention, one launch.

The dense fused step ran a (max_slots, width) rectangle — one wide prefill
row plus ``-1``-padded decode rows — so every launch paid
``max_slots x width`` tokens of attention for ~``width + batch`` useful
ones.  Here the token stream is *packed*: the prefill chunk and the N
single-token decode rows are laid back-to-back on the query axis and share
one grid.  Per-sequence ragged metadata replaces the rectangle:

  grid = (q_heads, q_blocks, kv_blocks); the kv dim is sequential
  ("arbitrary") carrying the online-softmax acc/m/l in VMEM scratch.

Raggedness enters through ``pltpu.PrefetchScalarGridSpec``: a scalar-
prefetched ``block_rows`` array (one cache row id per q block, available
*before* the grid body runs) drives the K/V/kv-pos index maps, so each q
block streams the KV of *its own sequence's* cache row — sequence i's
blocks revisit row[i], decode blocks jump straight to their slot's row.
The packing contract (ops.py) aligns each sequence's queries to ``block_q``
so a q block never spans two sequences; alignment holes carry INVALID_POS
positions and mask to zero output rows exactly like the dense path's pads.

GQA rides the same index-map trick as flash_prefill (h -> h // q_per_group);
masking (validity, causal, window, softcap) is bit-identical to ref.py.
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


def _ragged_kernel(rows_ref,                                  # scalar prefetch
                   qpos_ref, kpos_ref, q_ref, k_ref, v_ref,   # inputs
                   o_ref,                                     # outputs
                   acc_ref, m_ref, l_ref,                     # scratch
                   *, scale: float, softcap: Optional[float],
                   window: Optional[int], causal: bool, nk: int):
    del rows_ref  # consumed by the index maps, not the body
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                         # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                      # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap

    qp = qpos_ref[...]                                       # (bq, 1)
    kp = kpos_ref[0]                                         # (1, bkv)
    mask = (kp > (INVALID_POS // 2)) & (qp > (INVALID_POS // 2))
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
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def ragged_fused_hpd(
    q: jax.Array,                    # (H, P, hd)  packed; P % block_q == 0
    k: jax.Array,                    # (B, G, T, hd)
    v: jax.Array,
    q_positions: jax.Array,          # (1, P) int32  (INVALID_POS pads)
    kv_positions: jax.Array,         # (B, T) int32
    block_rows: jax.Array,           # (P // block_q,) int32  cache row per block
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    H, P, hd = q.shape
    B, G, T = k.shape[0], k.shape[1], k.shape[2]
    assert H % G == 0 and P % block_q == 0 and T % block_kv == 0, (H, G, P, T)
    qpg = H // G
    nq, nk = P // block_q, T // block_kv
    assert block_rows.shape == (nq,), (block_rows.shape, nq)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, softcap=softcap, window=window,
        causal=causal, nk=nk)

    # positions as (P, 1) / (B, 1, T) so every block's last two dims are
    # sublane/lane aligned or whole, as Mosaic requires at block_q = 8
    grid = (H, nq, nk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda h, qi, ki, rows: (qi, 0)),
            pl.BlockSpec((1, 1, block_kv),
                         lambda h, qi, ki, rows: (rows[qi], 0, ki)),
            pl.BlockSpec((1, block_q, hd), lambda h, qi, ki, rows: (h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda h, qi, ki, rows, _qpg=qpg:
                         (rows[qi], h // _qpg, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda h, qi, ki, rows, _qpg=qpg:
                         (rows[qi], h // _qpg, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda h, qi, ki, rows: (h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, P, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_rows, q_positions.reshape(P, 1), kv_positions[:, None, :],
      q, k, v)
    return out
