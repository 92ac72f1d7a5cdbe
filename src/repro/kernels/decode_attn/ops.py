"""Jit wrapper for the decode attention kernel.

Framework layout in: q (B, 1, H, hd), cache k/v (B, T, G, hd).  Groups the
queries by GQA group (rows = q_per_group, padded to a sublane multiple of
8), hands the kernel the cache as it is, counts each row's live KV blocks
from its positions, dispatches kernel or oracle, optionally returns
flash-decoding residuals for the context-parallel combine.  A head_dim that
is not a lane multiple (musicgen's 64) is padded to 128, which copies the
cache; so is a T that is not a block multiple (a LOCAL layer's ring + 1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attn import ref as ref_mod
from repro.kernels.decode_attn.decode_attn import (
    DEFAULT_BLOCK_KV,
    INVALID_POS,
    combine_partials,          # noqa: F401  (re-export)
    decode_attn_btgd,
)

#: bytes of one K (or V) block; caches wider than G*hd = 1024 get shorter
#: blocks, so double-buffered K and V and the block's scores stay well
#: inside the kernel's VMEM
_KV_BLOCK_BYTES = 1 << 20


def _pad_to(x, axis, mult, value=0):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def live_blocks(q_positions: jax.Array, kv_positions: jax.Array,
                block_kv: int) -> jax.Array:
    """(B,) blocks of ``block_kv`` positions up to and including each row's
    last valid position; 0 for an empty row or an invalid query."""
    T = kv_positions.shape[1]
    idx = jnp.arange(1, T + 1, dtype=jnp.int32)[None, :]
    live = jnp.max(jnp.where(kv_positions > INVALID_POS // 2, idx, 0), axis=1)
    live = jnp.where(q_positions[:, 0] > INVALID_POS // 2, live, 0)
    return ((live + block_kv - 1) // block_kv).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("window", "attn_softcap", "scale", "block_kv",
                     "interpret", "force_ref", "return_residuals"))
def decode_attention(
    q: jax.Array,                    # (B, 1, H, hd)
    k: jax.Array,                    # (B, T, G, hd)
    v: jax.Array,
    *,
    q_positions: jax.Array,          # (B, 1)
    kv_positions: jax.Array,         # (B, T)
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    scale: float,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
    force_ref: bool = False,
    return_residuals: bool = False,
):
    B, S, H, hd = q.shape
    assert S == 1, "decode takes exactly one new token per sequence"
    T, G = k.shape[1], k.shape[2]
    qpg = H // G

    rows = max(8, -(-qpg // 8) * 8)
    qg = q[:, 0].reshape(B, G, qpg, hd)
    qg = _pad_to(qg, 2, rows)
    qp = jnp.broadcast_to(q_positions, (B, rows)).astype(jnp.int32)
    qp = jnp.where(jnp.arange(rows)[None, :] < qpg, qp, INVALID_POS)

    use_kernel = interpret or jax.default_backend() == "tpu"
    if force_ref or not use_kernel:
        o, m, l = ref_mod.ref_decode_attn(
            qg, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), qp,
            kv_positions, scale=scale, window=window, softcap=attn_softcap)
    else:
        hdp = -(-hd // 128) * 128
        fit = _KV_BLOCK_BYTES // (G * hdp * k.dtype.itemsize) // 128 * 128
        bkv = max(128, min(block_kv, fit))
        bkv = T if T <= bkv else bkv
        kp = _pad_to(kv_positions, 1, bkv, value=INVALID_POS).astype(jnp.int32)
        kc = _pad_to(_pad_to(k, 1, bkv), 3, 128)
        vc = _pad_to(_pad_to(v, 1, bkv), 3, 128)
        o, m, l = decode_attn_btgd(
            _pad_to(qg, 3, 128).reshape(B, G * rows, hdp), kc, vc,
            jnp.tile(qp, (1, G)), kp, live_blocks(q_positions, kp, bkv),
            scale=scale, window=window, softcap=attn_softcap, block_kv=bkv,
            interpret=interpret)
        o = o.reshape(B, G, rows, hdp)[..., :hd]
        m, l = m.reshape(B, G, rows), l.reshape(B, G, rows)

    out = o[:, :, :qpg].reshape(B, 1, H, hd)
    if return_residuals:
        return out, m[:, :, :qpg].reshape(B, H), l[:, :, :qpg].reshape(B, H)
    return out
