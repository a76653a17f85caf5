"""Operations and bytes of the paged decode attention kernel.

One decoded token of one sequence with a visible window of w positions
(its cache plus itself), per layer: q.k over w keys and p.v over w
values for each of the H query heads (4*H*hd*w operations); it must read
the window's keys and values once for each of the Hkv kv heads, and its
query and output. Bytes are reckoned at the configuration's dtype
(``torch_dtype``), whatever dtype the program keeps its pool in, so the
yardstick reads the same work whatever implements it.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def cost(hf: dict, tokens: int, context: int) -> tuple:
    """(operations, bytes) for ``tokens`` decoded tokens with ``context``
    visible positions in all, over all layers."""
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    hd = hf.get("head_dim") or D // H
    Hkv, L = hf["num_key_value_heads"], hf["num_hidden_layers"]
    b = DTYPE_BYTES[hf["torch_dtype"]]
    flops = 4 * H * hd * context * L
    nbytes = (2 * Hkv * hd * context + 2 * H * hd * tokens) * b * L
    return float(flops), float(nbytes)
