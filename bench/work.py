"""Operations and bytes that the algorithm needs, and the chip's peaks.

Counts are of the work a call asks for, from its shapes: real tokens and
real context only, never the padding a program adds.  A roofline time is
the larger of operations over peak FLOP/s and bytes over peak bandwidth.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.model import head_dim

#: Published peaks per chip, keyed by ``device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


Segment = Tuple[int, int]      # (new tokens, cached context before them)


def _pairs(n: int, h: int) -> int:
    """Query-key pairs of n causal queries after h cached tokens."""
    return n * h + n * (n + 1) // 2


def attention_work(c: dict, segments: Iterable[Segment]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one attention kernel call over one layer: QK^T and
    PV for every causal pair; reads q and each row's valid K/V once,
    writes the output; bf16 operands."""
    H, G, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    flops = nbytes = 0.0
    for n, h in segments:
        flops += 4.0 * H * hd * _pairs(n, h)
        nbytes += 2.0 * (2 * n * H * hd + 2 * (h + n) * G * hd)
    return flops, nbytes


def layer_matmul_params(c: dict) -> int:
    """Weights one token multiplies by in one layer (top-k experts only)."""
    d, H, G = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd, ff = head_dim(c), c["intermediate_size"]
    attn = d * (H + 2 * G) * hd + H * hd * d
    E = int(c.get("num_local_experts") or 0)
    if E:
        return attn + d * E + 3 * d * ff * int(c["num_experts_per_tok"])
    return attn + 3 * d * ff


def step_flops(c: dict, segments: Iterable[Segment], logits_rows: int) -> float:
    """Model FLOPs of one step: every layer's projections and attention for
    the new tokens, and the output head for the rows whose logits it
    returns."""
    segments = list(segments)
    L = c["num_hidden_layers"]
    tokens = sum(n for n, _ in segments)
    f = 2.0 * tokens * layer_matmul_params(c) * L
    f += attention_work(c, segments)[0] * L
    f += 2.0 * logits_rows * c["hidden_size"] * c["vocab_size"]
    return f


def roofline_s(flops: float, nbytes: float, kind: str) -> float:
    p = peaks(kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
