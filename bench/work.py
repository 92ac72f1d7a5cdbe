"""Operations and bytes that the algorithm needs, and the chip's peaks.

Counts are of the work a call asks for, from its shapes: real tokens and
real context only, never the padding a program adds.  A roofline time is
the larger of operations over peak FLOP/s and bytes over peak bandwidth.

The counts themselves belong to the architecture: its module
(``configs/<reference>.py``, :func:`bench.model.architecture`) gives the
weights one token multiplies by in each layer (``layer_matmul_params(c,
layer)``) and, for each attention kernel, the work of one call over one
layer (``attention_work(c, segments, layer)`` for the model's attention
and the prefill kernel, and a function of its own for any other kernel).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench.model import architecture

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


def attention_work(c: dict, segments: Iterable[Segment],
                   layer: int = 0) -> Tuple[float, float]:
    """(FLOPs, bytes) of one attention kernel call over one layer."""
    return architecture(c).attention_work(c, list(segments), layer)


def layer_matmul_params(c: dict, layer: int = 0) -> int:
    """Weights one token multiplies by in one layer (top-k experts only)."""
    return architecture(c).layer_matmul_params(c, layer)


def step_flops(c: dict, segments: Iterable[Segment], logits_rows: int) -> float:
    """Model FLOPs of one step: each layer's projections and attention for
    the new tokens, summed layer by layer, and the output head for the
    rows whose logits it returns."""
    segments = list(segments)
    arch = architecture(c)
    tokens = sum(n for n, _ in segments)
    f = 0.0
    for layer in range(c["num_hidden_layers"]):
        f += 2.0 * tokens * arch.layer_matmul_params(c, layer)
        f += arch.attention_work(c, segments, layer)[0]
    f += 2.0 * logits_rows * c["hidden_size"] * c["vocab_size"]
    return f


def roofline_s(flops: float, nbytes: float, kind: str) -> float:
    p = peaks(kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
