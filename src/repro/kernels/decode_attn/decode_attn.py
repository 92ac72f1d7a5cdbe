"""Pallas TPU decode attention: one new token per sequence vs a long KV cache.

Memory-bound by design (the phase PD disaggregation gives its own workers).
The cache is read in its own ``(B, T, G, hd)`` layout, with no transposed
or relaid copy: grid (batch, kv_blocks), and each step loads one
``(block_kv, G, hd)`` K block and one V block, so every KV byte is fetched
once.  In VMEM a block is ``block_kv * G`` rows of ``hd`` lanes, ordered
(t, g); the queries are all G groups' ``q_per_group`` rows (each group
padded to 8), so one MXU product scores every query row against every
(t, g) row, and a group mask keeps each query's own group.  A product per
group would move the same K and V bytes through the MXU, but needs each
group's rows apart, which this layout gives only through a relaid copy of
the cache.  The kv grid dim is sequential and carries online-softmax state
in VMEM scratch.

Each row stops at its last live block: ``live_blocks[b]`` (scalar
prefetch) is one past the block holding the row's last valid position.
The K/V index maps clamp the block index there, so no DMA is issued past
it, and ``pl.when`` skips the compute.  Positions inside a live block are
masked exactly as before (validity, causality, window), so ring caches,
history inserted at an offset and reset slots stay exact.

``return_residuals=True`` additionally emits per-row (m, l) so a *sequence-
sharded* KV cache (context-parallel decode, DESIGN.md §5 — the beyond-paper
optimization) can run this same kernel per shard and combine partials with
two tiny collectives:  m* = max_i m_i;  l* = sum_i l_i e^{m_i-m*};
o* = sum_i o_i l_i e^{m_i-m*} / l*.  A shard with no live entries returns
l = 0, which the combine weighs as nothing.
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


def _decode_kernel(live_ref, qpos_ref, qgrp_ref, kpos_ref, kgrp_ref,
                   q_ref, k_ref, v_ref,
                   o_ref, m_out_ref, l_out_ref,
                   acc_ref, m_ref, l_ref,
                   *, scale: float, softcap: Optional[float],
                   window: Optional[int], nk: int):
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki < live_ref[b])
    def _block():
        bkv, G, hd = k_ref.shape[1:]
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        q = q_ref[0].astype(dt)                              # (G*rows, hd)
        k = k_ref[0].reshape(bkv * G, hd).astype(dt)         # rows (t, g)
        v = v_ref[0].reshape(bkv * G, hd).astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        qp = qpos_ref[0]                                     # (G*rows, 1)
        kp = kpos_ref[0]                                     # (1, bkv*G)
        mask = ((qgrp_ref[...] == kgrp_ref[...])
                & (kp > (INVALID_POS // 2)) & (kp <= qp))
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
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def decode_attn_btgd(
    q: jax.Array,                    # (B, G*rows, hd) rows = padded q_per_group
    k: jax.Array,                    # (B, T, G, hd): the cache's own layout
    v: jax.Array,
    q_positions: jax.Array,          # (B, G*rows) int32 (padded rows INVALID)
    kv_positions: jax.Array,         # (B, T) int32
    live_blocks: jax.Array,          # (B,) int32 blocks holding any valid position
    *,
    scale: float,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, GR, hd = q.shape
    T, G = k.shape[1], k.shape[2]
    assert GR % G == 0 and hd % 128 == 0 and T % block_kv == 0, (
        q.shape, k.shape, block_kv)
    nk = T // block_kv
    # the group of each query row and of each (t, g) cache row, and each
    # cache row's position
    q_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), GR // G)[:, None]
    kv_group = jnp.tile(jnp.arange(G, dtype=jnp.int32), block_kv)[None, :]
    kv_pos = jnp.repeat(kv_positions, G, axis=1)[:, None, :]

    def kv_block(b, ki, live):
        # past the row's last live block, stay on it: no new DMA
        return jnp.minimum(ki, jnp.maximum(live[b] - 1, 0))

    kernel = functools.partial(_decode_kernel, scale=scale, softcap=softcap,
                               window=window, nk=nk)
    # positions as (B, G*rows, 1) / (B, 1, T*G) so every block's last two
    # dims are sublane/lane aligned or whole, as Mosaic requires at any B
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, GR, 1), lambda b, ki, live: (b, 0, 0)),
                pl.BlockSpec((GR, 1), lambda b, ki, live: (0, 0)),
                pl.BlockSpec((1, 1, block_kv * G),
                             lambda b, ki, live: (b, 0, kv_block(b, ki, live))),
                pl.BlockSpec((1, block_kv * G), lambda b, ki, live: (0, 0)),
                pl.BlockSpec((1, GR, hd), lambda b, ki, live: (b, 0, 0)),
                pl.BlockSpec((1, block_kv, G, hd),
                             lambda b, ki, live: (b, kv_block(b, ki, live), 0, 0)),
                pl.BlockSpec((1, block_kv, G, hd),
                             lambda b, ki, live: (b, kv_block(b, ki, live), 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, GR, hd), lambda b, ki, live: (b, 0, 0)),
                pl.BlockSpec((1, GR, 1), lambda b, ki, live: (b, 0, 0)),
                pl.BlockSpec((1, GR, 1), lambda b, ki, live: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((GR, hd), jnp.float32),
                pltpu.VMEM((GR, 1), jnp.float32),
                pltpu.VMEM((GR, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, GR, hd), q.dtype),
            jax.ShapeDtypeStruct((B, GR, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, GR, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(live_blocks, q_positions[:, :, None], q_group, kv_pos, kv_group,
      q, k, v)
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
