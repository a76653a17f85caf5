"""Model operations of a dense decoder LM step, from its published sizes.

These are the operations the served work needs, not what an
implementation happens to compute: a prompt of p tokens needs its
projections and MLP for p tokens, causal attention over p(p+1)/2 pairs
and the output head for its last position only; a decoded token needs
them for one token, attention over its whole context and the head once.
Padding, recomputation after preemption and masked-out work are not
counted, so they show as a lower utilization.
"""
from __future__ import annotations


def dims(hf: dict):
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or D // H
    return (hf["num_hidden_layers"], D, H, hf["num_key_value_heads"], hd,
            hf["intermediate_size"], hf["vocab_size"])


def matmul_params_per_layer(hf: dict) -> int:
    """Weights a token multiplies through in one layer."""
    _, D, H, Hkv, hd, F, _ = dims(hf)
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D + 3 * D * F


def prefill_flops(hf: dict, tokens: int, tokens_sq: int, prompts: int
                  ) -> float:
    """Prompts totalling ``tokens`` tokens, ``tokens_sq`` = sum of p*p."""
    L, D, H, _, hd, _, V = dims(hf)
    dense = 2 * matmul_params_per_layer(hf) * L * tokens
    attn = 2 * H * hd * L * (tokens_sq + tokens)     # 4*H*hd * p(p+1)/2
    head = 2 * D * V * prompts
    return float(dense + attn + head)


def decode_flops(hf: dict, tokens: int, context: int) -> float:
    """``tokens`` decoded tokens attending to ``context`` positions in
    all (each to its own cache plus itself)."""
    L, D, H, _, hd, _, V = dims(hf)
    dense = 2 * matmul_params_per_layer(hf) * L * tokens
    attn = 4 * H * hd * L * context
    head = 2 * D * V * tokens
    return float(dense + attn + head)
