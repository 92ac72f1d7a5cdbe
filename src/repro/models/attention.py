"""Reference (pure-jnp / XLA) attention with GQA, sliding windows, history
offsets, softcaps and cross-attention.  The Pallas kernels in
``repro/kernels`` implement the same contract for TPU; ``impl="auto"``
dispatches there on TPU backends and here otherwise (CPU dry-runs and tests
always go through this path, which is also the oracle the kernels are checked
against).

GSPMD note: GQA is computed in *repeated-KV* layout (K/V broadcast to H
query heads) so that every attention tensor carries the head dim intact —
the (G, q_per_group) reshape makes the SPMD partitioner factor one mesh axis
across two dims and bounce layouts (involuntary full remats).  With explicit
logical constraints the partitioning is:
  head-divisible archs  -> logits sharded on heads (Megatron),
  non-divisible archs   -> logits sharded on q-seq (SP) for train/prefill,
  decode                -> logits sharded on kv-seq (context parallel; the
                           softmax reduction becomes two tiny all-reduces —
                           the flash-decoding combine, DESIGN.md §5/§6).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models.common import softcap as _softcap

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
INVALID_POS = -(2 ** 30)   # ring-cache slots that were never written

# Above this many score entries per batch row, the XLA path switches to the
# chunked online-softmax scan (flash-style memory behaviour in pure JAX); the
# dense einsum would materialize O(S*T) logits (34 TB for a 32k x 32k
# prefill).  Dense remains the small-shape oracle and the decode path (S=1).
_CHUNKED_THRESHOLD = 1 << 22
_KV_CHUNK = 2048


def _kernel_available() -> bool:
    return jax.default_backend() == "tpu"


def attention(
    q: jax.Array,                    # (B, S, H, hd)
    k: jax.Array,                    # (B, T, G, hd)
    v: jax.Array,
    *,
    q_positions: jax.Array,          # (B, S) int32
    kv_positions: jax.Array,         # (B, T) int32 (INVALID_POS for empty slots)
    causal: bool = True,
    window: Optional[int] = None,    # sliding window (None = unbounded)
    attn_softcap: Optional[float] = None,
    scale: float,
    impl: str = "auto",
) -> jax.Array:
    if impl == "auto":
        impl = "flash" if _kernel_available() else "ref"
    if impl == "flash" and causal and q.shape[1] == 1:
        # one token per row: read each row's live KV once, in the cache's
        # own layout, instead of the prefill kernel over every position
        from repro.kernels.decode_attn import ops as decode_ops
        return decode_ops.decode_attention(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions,
            window=window, attn_softcap=attn_softcap, scale=scale)
    if impl == "flash":
        from repro.kernels.flash_prefill import ops as flash_ops
        return flash_ops.flash_attention(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, window=window, attn_softcap=attn_softcap, scale=scale)
    S, T = q.shape[1], k.shape[1]
    if impl == "chunked" or (impl == "ref" and S > 1
                             and S * T > _CHUNKED_THRESHOLD):
        return chunked_ref_attention(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, window=window, attn_softcap=attn_softcap, scale=scale)
    return ref_attention(
        q, k, v, q_positions=q_positions, kv_positions=kv_positions,
        causal=causal, window=window, attn_softcap=attn_softcap, scale=scale)


def _repeat_kv(k: jax.Array, H: int) -> jax.Array:
    """(B, T, G, hd) -> (B, T, H, hd) by repeating each group qpg times."""
    G = k.shape[2]
    if G == H:
        return k
    return jnp.repeat(k, H // G, axis=2)


def _mask(qp, kp, causal, window):
    """qp (B,1,S,1), kp (B,1,1,T) -> bool (B,1,S,T)."""
    valid = kp > INVALID_POS // 2
    if causal:
        valid &= kp <= qp
    if window is not None:
        valid &= (qp - kp) < window
    return valid


def ref_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    scale: float,
) -> jax.Array:
    B, S, H, hd = q.shape
    kr = _repeat_kv(k, H)
    vr = _repeat_kv(v, H)
    q = shard(q, "batch", "seq", "heads", "head_dim")

    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
    logits = shard(logits, "batch", "heads", "seq", "kv_seq")
    logits = _softcap(logits, attn_softcap)

    qp = q_positions[:, None, :, None].astype(jnp.int32)
    kp = kv_positions[:, None, None, :].astype(jnp.int32)
    valid = _mask(qp, kp, causal, window)
    logits = jnp.where(valid, logits, NEG_INF)

    # Explicit softmax with the probs pinned to the logits' sharding: under
    # context-parallel decode (T sharded), jax.nn.softmax makes GSPMD
    # all-to-all the f32 logits to a heads layout (16+ MB/layer); pinning
    # keeps the reductions as KB-sized stat all-reduces (flash-decoding
    # combine).  §Perf cell A, iteration 3.
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(logits - m), 0.0)
    p = shard(p, "batch", "heads", "seq", "kv_seq")
    denom = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / jnp.where(denom == 0.0, 1.0, denom)
    out = jnp.einsum("bhst,bthd->bshd", probs, vr.astype(jnp.float32))
    out = out.astype(q.dtype)
    return shard(out, "batch", "seq", "heads", "head_dim")


def chunked_ref_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    attn_softcap: Optional[float] = None,
    scale: float,
    kv_chunk: int = _KV_CHUNK,
) -> jax.Array:
    """Online-softmax attention scanned over KV chunks (O(S*chunk) memory)."""
    B, S, H, hd = q.shape
    out_dtype = q.dtype
    T = k.shape[1]
    C = min(kv_chunk, T)
    pad = (-T) % C
    if pad:
        padw = [(0, 0), (0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        kv_positions = jnp.pad(kv_positions, [(0, 0), (0, pad)],
                               constant_values=INVALID_POS)
    nC = (T + pad) // C

    q = shard(q, "batch", "seq", "heads", "head_dim").astype(jnp.float32)
    qp = q_positions[:, None, :, None].astype(jnp.int32)         # (B,1,S,1)

    k_c = jnp.moveaxis(k.reshape(B, nC, C, -1, hd), 1, 0)        # (nC,B,C,G,hd)
    v_c = jnp.moveaxis(v.reshape(B, nC, C, -1, hd), 1, 0)
    p_c = jnp.moveaxis(kv_positions.reshape(B, nC, C), 1, 0)     # (nC,B,C)

    def body(carry, xs):
        m, l, acc = carry                          # (B,H,S), ..., (B,S,H,hd)
        kc, vc, pc = xs
        kr = _repeat_kv(kc, H).astype(jnp.float32)
        vr = _repeat_kv(vc, H).astype(jnp.float32)
        s = jnp.einsum("bshd,bthd->bhst", q, kr) * scale
        s = shard(s, "batch", "heads", "seq", None)
        if attn_softcap is not None:
            s = jnp.tanh(s / attn_softcap) * attn_softcap
        kp = pc[:, None, None, :]
        valid = _mask(qp, kp, causal, window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        delta = jnp.einsum("bhst,bthd->bshd", p, vr)
        acc = acc * jnp.moveaxis(alpha, 1, 2)[..., None] + delta
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, S, H, hd), jnp.float32)
    a0 = shard(a0, "batch", "seq", "heads", "head_dim")
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_c, v_c, p_c))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / jnp.moveaxis(l_safe, 1, 2)[..., None]
    out = out.astype(out_dtype)
    return shard(out, "batch", "seq", "heads", "head_dim")


def context_parallel_decode(
    q: jax.Array,                    # (B, 1, H, hd) batch-sharded
    k: jax.Array,                    # (B, T, G, hd) (batch, kv_seq)-sharded
    v: jax.Array,
    wo: jax.Array,                   # (H, hd, d) sharded on hd ("o_hd")
    *,
    q_positions: jax.Array,          # (B, 1)
    kv_positions: jax.Array,         # (B, T)
    window: Optional[int],
    attn_softcap: Optional[float],
    scale: float,
) -> jax.Array:
    """Explicit flash-decoding over a sequence-sharded KV cache, with the
    output projection folded in.  Returns the projected (B, 1, d).

    Fully-manual shard_map: each model shard runs decode attention on its KV
    slice (Pallas kernel on TPU, oracle on CPU); the flash-decoding combine
    is a psum_scatter of the weighted partial outputs onto the head_dim
    slices that wo is stored in, a (B,H) stat psum, a local partial dot and
    one (B,d) psum — ~0.5 MB/layer of ICI.  GSPMD's auto partitioner instead
    bounced an f32 all-to-all of 16.7 MB/layer through the wo dot (§Perf
    cell A, iterations 3-5).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import current_env
    from repro.kernels.decode_attn.ops import decode_attention

    env = current_env()
    batch_axes = env.rules.get("batch")
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in (batch_axes or ())
                       if a in env.mesh.axis_names)
    model_axis = "model"
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)

    def body(qb, kb, vb, qpb, kpb, wob):
        o, m, l = decode_attention(
            qb, kb, vb, q_positions=qpb, kv_positions=kpb, window=window,
            attn_softcap=attn_softcap, scale=scale, return_residuals=True)
        # weighted partial (numerator of the flash-decoding combine)
        m_star = jax.lax.pmax(m, model_axis)                   # (B, H)
        w = l * jnp.exp(m - m_star)
        num = o[:, 0].astype(jnp.float32) * w[..., None]       # (B, H, hd)
        num_sh = jax.lax.psum_scatter(num, model_axis,
                                      scatter_dimension=2, tiled=True)
        denom = jax.lax.psum(w, model_axis)                    # (B, H)
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_sh = (num_sh / denom[..., None]).astype(qb.dtype)    # (B, H, hd/S)
        part = jnp.einsum("bhp,hpd->bd", o_sh, wob)            # local partial
        out = jax.lax.psum(part, model_axis)                   # (B, d)
        return out[:, None]

    fn = jax.shard_map(
        body, mesh=env.mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, model_axis, None, None),
                  P(bspec, model_axis, None, None), P(bspec, None),
                  P(bspec, model_axis), P(None, model_axis, None)),
        out_specs=P(bspec, None, None),
        axis_names=frozenset(set(batch_axes) | {model_axis}),
        check_vma=False)
    return fn(q, k, v, q_positions, kv_positions, wo)


def cross_attention(
    q: jax.Array,                    # (B, S, H, hd)
    k: jax.Array,                    # (B, T_img, G, hd)
    v: jax.Array,
    *,
    scale: float,
    attn_softcap: Optional[float] = None,
) -> jax.Array:
    """Unmasked attention to frontend embeddings (vlm cross layers)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    qpos = jnp.zeros((B, S), jnp.int32)
    kpos = jnp.zeros((B, T), jnp.int32)
    return ref_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                         causal=False, window=None, attn_softcap=attn_softcap,
                         scale=scale)
