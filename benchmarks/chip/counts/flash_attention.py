"""Operations and bytes of causal prefill attention (flash_attention).

A prompt of p tokens, per layer: q.k and p.v over the p(p+1)/2 causal
pairs for each of the H query heads (2*H*hd*(p*p + p) operations); it
must read q, k, v and write the output once. Bytes at the
configuration's dtype. Padding up to the engine's prompt bucket is not
work the prompt needs and is not counted.
"""
from __future__ import annotations

from .paged_decode import DTYPE_BYTES


def cost(hf: dict, tokens: int, tokens_sq: int) -> tuple:
    """(operations, bytes) for prompts of ``tokens`` tokens in all,
    ``tokens_sq`` the sum of their squares, over all layers."""
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or D // H
    Hkv, L = hf["num_key_value_heads"], hf["num_hidden_layers"]
    b = DTYPE_BYTES[hf["torch_dtype"]]
    flops = 2 * H * hd * (tokens_sq + tokens) * L
    nbytes = (2 * H * hd + 2 * Hkv * hd) * tokens * b * L
    return float(flops), float(nbytes)
