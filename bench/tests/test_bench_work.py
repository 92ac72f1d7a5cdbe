"""Operation and byte counts against hand counts at small shapes."""
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

import pytest  # noqa: E402

from bench import work  # noqa: E402

C = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
     "intermediate_size": 16, "vocab_size": 10, "num_hidden_layers": 3,
     "reference": "decoder"}


def test_attention_work_by_hand():
    # head_dim 2; one segment of 3 new tokens after 5 cached:
    # pairs = 3*5 + (1+2+3) = 21; flops = 4 * H(4) * hd(2) * 21 = 672
    # bytes = 2 * (2*3*4*2 [q and out] + 2*(5+3)*2*2 [k and v]) = 2*(48+64)
    f, b = work.attention_work(C, [(3, 5)])
    assert f == 672 and b == 224
    f2, b2 = work.attention_work(C, [(3, 5), (1, 0)])
    assert f2 == 672 + 4 * 4 * 2 * 1 and b2 == 224 + 2 * (2 * 4 * 2 + 2 * 1 * 2 * 2)


def test_step_flops_by_hand():
    # per layer: attention 8*(4+2*2)*2 + 4*2*8 = 128 + 64 = 192; mlp 3*8*16 = 384
    assert work.layer_matmul_params(C) == 576
    # 2 tokens, 3 layers: 2*2*576*3 = 6912; attention pairs (2 after 0) = 3:
    # 4*4*2*3*3 layers = 288; head: 2*1*8*10 = 160
    assert work.step_flops(C, [(2, 0)], 1) == 6912 + 288 + 160


def test_moe_counts_only_the_routed_experts():
    m = dict(C, num_local_experts=8, num_experts_per_tok=2)
    assert work.layer_matmul_params(m) == 192 + 8 * 8 + 3 * 8 * 16 * 2


def test_roofline_takes_the_larger_bound():
    p = work.peaks("TPU v5 lite")
    assert work.roofline_s(p["bf16_flops"], 0, "TPU v5 lite") == pytest.approx(1.0)
    assert work.roofline_s(0, 2 * p["hbm_bytes_per_s"], "TPU v5 lite") == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
