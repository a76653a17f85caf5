"""Random weights for a dense decoder LM, made on the device from a seed.

One jitted call draws every weight of a Qwen2/Llama-style model in the
dtype it is served in. The result is a plain dict with one stacked array
per kind of weight (leading axis: layer), named as the published
checkpoints name them; ``reference.dense_lm`` reads that dict, and
``program_params`` re-keys the same arrays into the tree the program
takes, so both see identical values.
"""
from __future__ import annotations

import numpy as np

INIT_STD_EMBED = 0.02     # embedding rows
INIT_STD_BIAS = 0.1       # q/k/v biases (QKV-bias models)
INIT_STD_NORM = 0.05      # spread of RMSNorm weights around 1


def key_from_seed(seed: int):
    """A JAX key from any whole-number seed: ``jax.random.key`` keeps only
    the low 32 bits, so large seeds go through numpy first."""
    import jax

    return jax.random.key(int(np.random.default_rng(seed).integers(2 ** 31)))


def padded_vocab(vocab: int) -> int:
    """Rows of the embedding table: the vocabulary rounded up to 256."""
    return -(-vocab // 256) * 256


def make_dense(hf: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """All weights of the model ``hf`` (a published ``config.json``'s
    keys), as a dict of device arrays in ``dtype``, from one jitted call.
    Linear weights are stored (in, out). Embedding rows past the
    vocabulary are zero."""
    import jax

    return jax.jit(dense_drawer(hf, dtype))(key_from_seed(seed))


def dense_drawer(hf: dict, dtype: str = "bfloat16"):
    """The function ``make_dense`` jits: key -> weights."""
    import jax
    import jax.numpy as jnp

    L, D = hf["num_hidden_layers"], hf["hidden_size"]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or D // H
    F, V = hf["intermediate_size"], hf["vocab_size"]
    Vp = padded_vocab(V)
    bias = has_qkv_bias(hf)
    dt = jnp.dtype(dtype)

    def draw(key):
        ks = iter(jax.random.split(key, 16))

        def lin(shape):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / np.sqrt(shape[-2])).astype(dt)

        def norm(shape):
            return (1.0 + INIT_STD_NORM * jax.random.normal(
                next(ks), shape, jnp.float32)).astype(dt)

        emb = jax.random.normal(next(ks), (Vp, D), jnp.float32) * INIT_STD_EMBED
        emb = jnp.where(jnp.arange(Vp)[:, None] < V, emb, 0.0).astype(dt)
        w = {
            "embed_tokens": emb,
            "input_layernorm": norm((L, D)),
            "q_proj": lin((L, D, H * hd)),
            "k_proj": lin((L, D, Hkv * hd)),
            "v_proj": lin((L, D, Hkv * hd)),
            "o_proj": lin((L, H * hd, D)),
            "post_attention_layernorm": norm((L, D)),
            "gate_proj": lin((L, D, F)),
            "up_proj": lin((L, D, F)),
            "down_proj": lin((L, F, D)),
            "norm": norm((D,)),
        }
        if bias:
            for name, width in (("q_bias", H * hd), ("k_bias", Hkv * hd),
                                ("v_bias", Hkv * hd)):
                w[name] = (INIT_STD_BIAS * jax.random.normal(
                    next(ks), (L, width), jnp.float32)).astype(dt)
        if not hf.get("tie_word_embeddings", False):
            w["lm_head"] = (jax.random.normal(next(ks), (D, Vp), jnp.float32)
                            * INIT_STD_EMBED).astype(dt)
        return w

    return draw


def has_qkv_bias(hf: dict) -> bool:
    """Qwen2 puts a bias on q, k and v; Llama-style configs say so with
    ``attention_bias``."""
    return bool(hf.get("attention_bias", hf.get("model_type") == "qwen2"))


def program_params(w: dict) -> dict:
    """The program's parameter tree (``repro.models``) over the same
    arrays. Its RMSNorm scales by ``1 + scale``, so a norm weight ``g``
    goes in as ``g - 1`` (exact in float32 for weights near 1)."""
    import jax.numpy as jnp

    def scale(g):
        return g.astype(jnp.float32) - 1.0

    attn = {"wq": w["q_proj"], "wk": w["k_proj"], "wv": w["v_proj"],
            "wo": w["o_proj"]}
    if "q_bias" in w:
        attn.update(bq=w["q_bias"], bk=w["k_bias"], bv=w["v_bias"])
    params = {
        "embed": w["embed_tokens"],
        "blocks": {
            "ln1": scale(w["input_layernorm"]),
            "attn": attn,
            "ln2": scale(w["post_attention_layernorm"]),
            "mlp": {"wg": w["gate_proj"], "wi": w["up_proj"],
                    "wo": w["down_proj"]},
        },
        "final_ln": scale(w["norm"]),
    }
    if "lm_head" in w:
        params["head"] = w["lm_head"]
    return params
