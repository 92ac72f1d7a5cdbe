"""Pallas kernel validation: interpret-mode vs pure-jnp oracles across a
shape/dtype/feature sweep (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn.ops import decode_attention
from repro.kernels.flash_prefill.ops import flash_attention

KEY = jax.random.PRNGKey(0)


def _mk(B, S, H, G, hd, T, hist, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, T, G, hd), dtype)
    v = jax.random.normal(ks[2], (B, T, G, hd), dtype)
    qpos = jnp.broadcast_to(hist + jnp.arange(S, dtype=jnp.int32), (B, S))
    kpos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    kpos = jnp.where(kpos < hist + S, kpos, -(2 ** 30))
    return q, k, v, qpos, kpos


SWEEP = [
    # B, S, H, G, hd, T, hist, window, softcap, dtype
    (2, 32, 4, 2, 64, 32, 0, None, None, jnp.float32),
    (1, 48, 8, 8, 64, 48, 0, None, None, jnp.float32),       # MHA, pad blocks
    (2, 32, 4, 2, 64, 96, 40, None, None, jnp.float32),      # incremental
    (2, 32, 4, 2, 64, 96, 40, 16, None, jnp.float32),        # sliding window
    (2, 32, 8, 2, 64, 64, 0, None, 50.0, jnp.float32),       # softcap (gemma2)
    (1, 8, 10, 2, 112, 40, 24, None, None, jnp.float32),     # hd=112 (kimi)
    (2, 32, 4, 2, 64, 64, 0, None, None, jnp.bfloat16),
    (1, 1, 4, 2, 64, 33, 32, None, None, jnp.float32),       # decode-like
]


@pytest.mark.parametrize("B,S,H,G,hd,T,hist,window,softcap,dtype", SWEEP)
def test_flash_prefill_vs_oracle(B, S, H, G, hd, T, hist, window, softcap, dtype):
    q, k, v, qpos, kpos = _mk(B, S, H, G, hd, T, hist, dtype)
    scale = hd ** -0.5
    out_k = flash_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                            causal=True, window=window, attn_softcap=softcap,
                            scale=scale, block_q=16, block_kv=16, interpret=True)
    out_r = flash_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                            causal=True, window=window, attn_softcap=softcap,
                            scale=scale, force_ref=True)
    tol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=tol, rtol=tol)


DEC_SWEEP = [
    # B, H, G, hd, T, pos, window, softcap
    (2, 8, 2, 64, 256, 200, None, None),
    (2, 8, 2, 64, 256, 200, 64, None),
    (1, 40, 8, 128, 512, 300, None, 50.0),    # qwen heads, qpg=5 pad
    (2, 10, 1, 128, 256, 100, None, None),    # MQA (recurrentgemma-like)
    (1, 64, 8, 112, 256, 60, None, None),     # kimi head_dim
    (2, 24, 24, 64, 128, 90, None, None),     # MHA (musicgen)
    # per-row last positions (-1: empty slot): empty, mid-block, on a
    # block boundary (128-position blocks), full
    (4, 40, 8, 128, 512, (-1, 299, 255, 511), None, None),   # qwen widths
    (4, 32, 8, 128, 512, (511, -1, 127, 200), None, None),   # mixtral widths
    (3, 16, 4, 64, 384, (383, 130, -1), 64, None),   # hd 64: padded lanes
    # a ring of 256 plus the new token (LOCAL layers): one row wrapped
    # (positions not contiguous in the cache), one not yet full
    (2, 8, 2, 128, 257, ("ring", 600, 100), 256, None),
]


def _dec_positions(B, T, pos):
    """(q_positions (B, 1), kv_positions (B, T)) for a DEC_SWEEP ``pos``:
    one last position for every row, one per row (-1 for an empty slot),
    or ("ring", *last) for a ring of T-1 slots followed by the new token."""
    inv = -(2 ** 30)
    if isinstance(pos, int):
        pos = (pos,) * B
    kpos = np.full((B, T), inv, np.int32)
    qpos = np.full((B, 1), inv, np.int32)
    if pos[0] == "ring":
        W = T - 1
        for b, p in enumerate(pos[1:]):
            hist = np.arange(max(0, p - W), p)
            kpos[b, hist % W] = hist
            kpos[b, W] = qpos[b, 0] = p
        return jnp.asarray(qpos), jnp.asarray(kpos)
    for b, p in enumerate(pos):
        if p >= 0:
            kpos[b, :p + 1] = np.arange(p + 1)
            qpos[b, 0] = p
    return jnp.asarray(qpos), jnp.asarray(kpos)


@pytest.mark.parametrize("B,H,G,hd,T,pos,window,softcap", DEC_SWEEP)
def test_decode_attn_vs_oracle(B, H, G, hd, T, pos, window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, T, G, hd))
    v = jax.random.normal(ks[2], (B, T, G, hd))
    qpos, kpos = _dec_positions(B, T, pos)
    scale = hd ** -0.5
    o1 = decode_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                          window=window, attn_softcap=softcap, scale=scale,
                          block_kv=128, interpret=True)
    o2 = decode_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                          window=window, attn_softcap=softcap, scale=scale,
                          force_ref=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=5e-5,
                               rtol=5e-5)
    empty = np.asarray(qpos[:, 0]) < 0
    assert not np.asarray(o1)[empty].any() and not np.asarray(o2)[empty].any()


def test_live_blocks_stop_at_each_rows_last_valid_position():
    from repro.kernels.decode_attn.ops import live_blocks
    qpos, kpos = _dec_positions(4, 512, (-1, 299, 255, 511))
    assert live_blocks(qpos, kpos, 128).tolist() == [0, 3, 2, 4]
    qpos, kpos = _dec_positions(2, 257, ("ring", 600, 100))
    assert live_blocks(qpos, kpos, 128).tolist() == [3, 3]
    # a row whose query is padding reads nothing, whatever its cache holds
    assert live_blocks(jnp.full((2, 1), -(2 ** 30), jnp.int32), kpos,
                       128).tolist() == [0, 0]


@pytest.mark.parametrize("pos", [230, 100])     # 100: shard 2 holds nothing
@pytest.mark.parametrize("kernel", [False, True])
def test_decode_residual_combine(pos, kernel):
    """Flash-decoding shard combine reproduces the unsharded result."""
    B, H, G, hd, T = 2, 8, 2, 64, 256
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, T, G, hd))
    v = jax.random.normal(ks[2], (B, T, G, hd))
    qpos, kpos = _dec_positions(B, T, pos)
    mode = dict(block_kv=128, interpret=True) if kernel else dict(
        force_ref=True)
    full = decode_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                            scale=hd ** -0.5, force_ref=True)
    parts = []
    for sl in (slice(0, 128), slice(128, 256)):
        parts.append(decode_attention(
            q, k[:, sl], v[:, sl], q_positions=qpos, kv_positions=kpos[:, sl],
            scale=hd ** -0.5, return_residuals=True, **mode))
    if pos < 128:
        assert not np.asarray(parts[1][2]).any()
    m_star = jnp.maximum(parts[0][1], parts[1][1])
    w = [p[2] * jnp.exp(p[1] - m_star) for p in parts]
    den = w[0] + w[1]
    num = (parts[0][0].astype(jnp.float32) * w[0][:, None, :, None]
           + parts[1][0].astype(jnp.float32) * w[1][:, None, :, None])
    comb = num / den[:, None, :, None]
    np.testing.assert_allclose(np.asarray(comb), np.asarray(full), atol=1e-5)


@pytest.mark.parametrize("S,kernel", [(1, "decode_attention"),
                                      (4, "flash_attention")])
def test_flash_impl_dispatches_by_query_length(monkeypatch, S, kernel):
    """impl="flash" sends one-token calls to the decode kernel and longer
    chunks to the prefill kernel."""
    from repro.kernels.decode_attn import ops as decode_ops
    from repro.kernels.flash_prefill import ops as flash_ops
    from repro.models.attention import attention

    called = []
    for mod, name in ((decode_ops, "decode_attention"),
                      (flash_ops, "flash_attention")):
        def spy(*a, _f=getattr(mod, name), _n=name, **kw):
            called.append(_n)
            return _f(*a, **kw)
        monkeypatch.setattr(mod, name, spy)
    B, H, G, hd, T = 2, 8, 2, 64, 32
    q, k, v, qpos, kpos = _mk(B, S, H, G, hd, T, T - S, jnp.float32)
    out = attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                    scale=hd ** -0.5, impl="flash")
    ref = attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                    scale=hd ** -0.5, impl="ref")
    assert called == [kernel]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_chunked_attention_vs_dense():
    from repro.models.attention import chunked_ref_attention, ref_attention
    B, S, H, G, hd, T, hist = 2, 16, 4, 2, 32, 48, 24
    q, k, v, qpos, kpos = _mk(B, S, H, G, hd, T, hist, jnp.float32)
    a = ref_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                      scale=hd ** -0.5)
    b = chunked_ref_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                              scale=hd ** -0.5, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# ragged fused chunk+decode megakernel (DESIGN.md §15)
# ---------------------------------------------------------------------------

def _mk_ragged(segments, B, T, H, G, hd, dtype, align):
    """Build a packed query stream + batched KV cache from
    ``(row, length, cache_len)`` segment specs."""
    from repro.kernels.ragged_fused.ops import build_pack

    pack = build_pack([(r, np.zeros(n, np.int32), c)
                       for r, n, c in segments], align=align)
    P = pack["total"]
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (P, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, T, G, hd), dtype)
    v = jax.random.normal(ks[2], (B, T, G, hd), dtype)
    kpos = np.full((B, T), -(2 ** 30), np.int32)
    for r, n, c in segments:
        kpos[r, :c + n] = np.arange(c + n)
    return (q, k, v, jnp.asarray(pack["rows"]),
            jnp.asarray(pack["positions"]), jnp.asarray(kpos), pack)


RAGGED_SWEEP = [
    # segments [(row, len, cache_len)], B, T, H, G, hd, window, softcap, dtype
    # standard piggyback: one chunk + decode rows
    ([(0, 32, 8), (1, 1, 20), (2, 1, 5), (3, 1, 40)],
     4, 64, 4, 2, 64, None, None, jnp.float32),
    # odd lengths / not multiples of the pad multiple
    ([(0, 17, 3), (1, 7, 11), (2, 1, 29)],
     3, 48, 4, 2, 64, None, None, jnp.float32),
    # single-token-only pack (pure continuous-batching decode)
    ([(0, 1, 30), (1, 1, 12), (2, 1, 47), (3, 1, 3)],
     4, 48, 8, 2, 64, None, None, jnp.float32),
    # prefill-only pack (no piggybackers)
    ([(1, 48, 0)], 2, 48, 4, 4, 64, None, None, jnp.float32),
    # mixed + sliding window + softcap (gemma2-style)
    ([(0, 19, 10), (2, 1, 33), (3, 5, 0)],
     4, 64, 8, 4, 64, 16, 50.0, jnp.float32),
    # bf16 mixed pack
    ([(0, 23, 5), (1, 1, 31), (3, 1, 9)],
     4, 64, 4, 2, 64, None, None, jnp.bfloat16),
]


@pytest.mark.parametrize("segments,B,T,H,G,hd,window,softcap,dtype",
                         RAGGED_SWEEP)
def test_ragged_fused_vs_oracle(segments, B, T, H, G, hd, window, softcap,
                                dtype):
    """Interpret-mode megakernel vs the pure-jnp oracle across ragged packs.
    align == block_q so no kernel q block spans two sequences."""
    from repro.kernels.ragged_fused.ops import ragged_attention

    bq = 16
    q, k, v, rows, qpos, kpos, _ = _mk_ragged(segments, B, T, H, G, hd,
                                              dtype, align=bq)
    scale = hd ** -0.5
    out_k = ragged_attention(q, k, v, q_rows=rows, q_positions=qpos,
                             kv_positions=kpos, causal=True, window=window,
                             attn_softcap=softcap, scale=scale, block_q=bq,
                             block_kv=16, interpret=True)
    out_r = ragged_attention(q, k, v, q_rows=rows, q_positions=qpos,
                             kv_positions=kpos, causal=True, window=window,
                             attn_softcap=softcap, scale=scale,
                             force_ref=True)
    tol = 5e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               atol=tol, rtol=tol)
    # alignment holes and pad tail must produce exactly-zero rows on both
    pad = np.asarray(rows) < 0
    if pad.any():
        assert np.abs(np.asarray(out_k, np.float32)[pad]).max() == 0.0
        assert np.abs(np.asarray(out_r, np.float32)[pad]).max() == 0.0


def test_ragged_vs_flash_per_sequence():
    """Each packed segment must equal a standalone flash_attention call on
    its own sequence — raggedness is layout, not semantics."""
    from repro.kernels.ragged_fused.ops import ragged_attention

    segments = [(0, 24, 6), (1, 1, 17), (2, 9, 0)]
    B, T, H, G, hd = 3, 48, 4, 2, 64
    q, k, v, rows, qpos, kpos, pack = _mk_ragged(
        segments, B, T, H, G, hd, jnp.float32, align=1)
    out = ragged_attention(q, k, v, q_rows=rows, q_positions=qpos,
                           kv_positions=kpos, causal=True, scale=hd ** -0.5,
                           force_ref=True)
    for (r, n, c), start in zip(segments, np.asarray(pack["starts"])):
        ref = flash_attention(
            q[None, start:start + n], k[r:r + 1], v[r:r + 1],
            q_positions=qpos[None, start:start + n],
            kv_positions=kpos[r:r + 1], causal=True, scale=hd ** -0.5,
            force_ref=True)
        np.testing.assert_allclose(np.asarray(out[start:start + n]),
                                   np.asarray(ref[0]), atol=2e-5, rtol=2e-5)
