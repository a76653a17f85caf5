"""Plain float32 forward pass of a dense decoder LM (Qwen2/Llama style).

It follows the published architecture: token embedding; per layer
RMSNorm, grouped-query attention with rotary embeddings (rotate-half,
``rope_theta``) and optional q/k/v biases, a residual, RMSNorm, a SwiGLU
MLP and a residual; a final RMSNorm and the output head (the embedding,
when tied). It runs one whole sequence with no cache, no kernels and no
batching, in float32 with matrix products at ``highest`` precision, and
a layer at a time (``lax.scan``), so that it fits on the chip beside
nothing else.

``served_gaps`` is the comparison that decides a serving cell's
``correct``: for each token the program served, how far its reference
logit lies below the reference's best one. With ``control=True`` it is
the same reading for the reference itself computed in fp8 (e4m3, scaled per
tensor for activations and per output channel for weights): the token
that precision puts first, read against the float32 logits.
"""
from __future__ import annotations

import functools

import numpy as np

FP8_MAX = 448.0          # largest finite float8_e4m3fn
# The published config keys the forward pass reads.
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size", "rms_norm_eps", "rope_theta")


def _dims(hf: dict):
    D, H = hf["hidden_size"], hf["num_attention_heads"]
    return (D, H, hf["num_key_value_heads"], hf.get("head_dim") or D // H,
            hf["vocab_size"])


def _fp8(a, axes):
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _matmul(precision: str):
    """x (..., in) @ w (in, out) in float32, or through fp8."""
    if precision == "f32":
        return lambda x, w: x @ w
    assert precision == "fp8", precision
    return lambda x, w: _fp8(x, None) @ _fp8(w, 0)


def _rms(x, g, eps):
    import jax

    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _rope(x, theta: float):
    """x: (S, heads, hd), positions 0..S-1, rotate-half convention."""
    import jax.numpy as jnp

    S, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(S)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden_states(w: dict, hf: dict, tokens, precision: str = "f32"):
    """Final-norm hidden states (S, D) of one sequence."""
    import jax
    import jax.numpy as jnp

    D, H, Hkv, hd, _ = _dims(hf)
    eps = float(hf["rms_norm_eps"])
    theta = float(hf["rope_theta"])
    mm = _matmul(precision)
    f32 = lambda a: a.astype(jnp.float32)
    S = tokens.shape[0]
    causal = jnp.tril(jnp.ones((S, S), bool))
    names = ["input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
             "post_attention_layernorm", "gate_proj", "up_proj", "down_proj"]
    if "q_bias" in w:
        names += ["q_bias", "k_bias", "v_bias"]

    def layer(x, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        h = _rms(x, lw["input_layernorm"], eps)
        q, k, v = mm(h, lw["q_proj"]), mm(h, lw["k_proj"]), mm(h, lw["v_proj"])
        if "q_bias" in lw:
            q, k, v = q + lw["q_bias"], k + lw["k_bias"], v + lw["v_bias"]
        q = _rope(q.reshape(S, H, hd), theta)
        k = _rope(k.reshape(S, Hkv, hd), theta)
        v = v.reshape(S, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
        x = x + mm(o, lw["o_proj"])
        h = _rms(x, lw["post_attention_layernorm"], eps)
        g = mm(h, lw["gate_proj"])
        x = x + mm(jax.nn.silu(g) * mm(h, lw["up_proj"]), lw["down_proj"])
        return x, None

    x = f32(w["embed_tokens"])[tokens]
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in names})
    return _rms(x, f32(w["norm"]), eps)


def logits_at(w: dict, hf: dict, tokens, rows, precision: str = "f32"):
    """Logits (len(rows), vocab) at the given positions."""
    import jax.numpy as jnp

    V = hf["vocab_size"]
    h = hidden_states(w, hf, tokens, precision)[rows]
    head = (w["embed_tokens"][:V].T if "lm_head" not in w
            else w["lm_head"][:, :V])
    return _matmul(precision)(h, head.astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _gap_fn(hf_items: tuple, control: bool):
    import jax
    import jax.numpy as jnp

    hf = dict(hf_items)

    def fn(w, tokens, rows, served):
        with jax.default_matmul_precision("highest"):
            ref = logits_at(w, hf, tokens, rows, "f32")
            if control:
                low = logits_at(w, hf, tokens, rows, "fp8")
                served = jnp.argmax(low, axis=-1)
        picked = jnp.take_along_axis(ref, served[:, None], axis=1)[:, 0]
        return ref.max(axis=-1) - picked

    return jax.jit(fn)


def _inputs(prompt, out, seq_pad: int, rows_pad: int):
    """Fed tokens (prompt + served[:-1]) padded to ``seq_pad``; the row
    that predicts each served token, padded to ``rows_pad`` by repeating
    the last one."""
    n_p, n_s = len(prompt), len(out)
    seq = np.zeros(seq_pad, np.int32)
    fed = list(prompt) + list(out[:-1])
    if len(fed) > seq_pad or n_s > rows_pad:
        raise ValueError(f"sequence {len(fed)}/{n_s} exceeds the reference's "
                         f"shape {seq_pad}/{rows_pad}")
    seq[: len(fed)] = fed
    rows = np.full(rows_pad, n_p - 1 + n_s - 1, np.int32)
    rows[:n_s] = n_p - 1 + np.arange(n_s)
    served = np.zeros(rows_pad, np.int32)
    served[:n_s] = out
    served[n_s:] = out[-1]
    return seq, rows, served


def served_gaps(w, hf, prompt, out, seq_pad: int, rows_pad: int,
                control: bool = False) -> np.ndarray:
    """Per served token, the reference's best logit minus the logit of
    the token served (``control``: of the token fp8 puts first)."""
    import jax.numpy as jnp

    seq, rows, served = _inputs(prompt, out, seq_pad, rows_pad)
    fn = _gap_fn(tuple((k, hf[k]) for k in KEYS if k in hf), control)
    gaps = fn(w, jnp.asarray(seq), jnp.asarray(rows), jnp.asarray(served))
    return np.asarray(gaps)[: len(out)]
