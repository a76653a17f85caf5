"""Operation and byte counts at shapes worked out by hand, and the peak
table."""
import pytest

from benchmarks.chip import harness, readers
from benchmarks.chip.counts import dense_lm_step, flash_attention, paged_decode

# A model small enough to count by hand: L=2, D=8, H=2, Hkv=1, hd=4,
# F=16, V=10.
HF = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
      "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
      "vocab_size": 10, "torch_dtype": "bfloat16"}


def test_matmul_params_per_layer():
    # q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64 + 64 + 64 + 384
    assert dense_lm_step.matmul_params_per_layer(HF) == 576


def test_prefill_flops_one_prompt_of_three():
    # dense 2*576*2 layers*3 tokens = 6912; attention 4*H*hd per pair,
    # 6 causal pairs, 2 layers: 4*2*4*6*2 = 384; head 2*8*10 once = 160.
    assert dense_lm_step.prefill_flops(HF, tokens=3, tokens_sq=9,
                                       prompts=1) == 6912 + 384 + 160


def test_decode_flops_two_tokens():
    # dense 2*576*2*2 = 4608; attention 4*2*4*2 layers*(5+6) = 704;
    # head 2*8*10*2 = 320.
    assert dense_lm_step.decode_flops(HF, tokens=2, context=11) == \
        4608 + 704 + 320


def test_paged_decode_cost():
    # One token, window 5: flops 4*2*4*5*2 = 320; bytes: k,v 2*1*4*5
    # + q,out 2*2*4, at 2 B, 2 layers = (40 + 16)*2*2 = 224.
    assert paged_decode.cost(HF, tokens=1, context=5) == (320.0, 224.0)


def test_flash_attention_cost():
    # p=3: flops 2*2*4*(9+3)*2 = 384; bytes (2*2*4 + 2*1*4)*3*2 B*2 = 288.
    assert flash_attention.cost(HF, tokens=3, tokens_sq=9) == (384.0, 288.0)


def test_roofline_takes_the_binding_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 200 flops -> 2 s at peak; 50 bytes -> 5 s: memory bound, 5 s of 10.
    assert readers.roofline_pct(200, 50, 10.0, peaks) == 50.0
    assert readers.roofline_pct(0, 0, 1.0, peaks) is None


def test_peaks_keyed_by_device_kind():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
