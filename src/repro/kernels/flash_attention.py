"""Pallas TPU flash attention (causal, GQA), VMEM-tiled with BlockSpecs.

Online-softmax blocked attention: grid = (batch, q_heads, q_blocks,
kv_blocks) with the kv dimension innermost — TPU grids execute sequentially,
so the running max / denominator / accumulator live in VMEM scratch across
kv steps and the output tile is written once on the last kv step.

Supports Sq != Skv with decode alignment (query i sits at absolute position
Skv - Sq + i), which is what the serving path needs (Sq == 1 against a long
KV cache), and GQA via the kv-head index map (h // group).

Layout: the kernel runs head-major — q (B, Hq, Sq, D), k/v (B, Hkv, Skv, D)
— so every block ends in (block_tokens, D), the (8, 128)-tileable shape the
TPU compiler requires; the wrapper transposes from and back to the model's
(B, S, H, D). ``online_softmax_step`` is the per-block update shared with
the decode and paged kernels.

Oracle: ref.attention_ref. Validated in interpret mode on CPU; compiled for
TPU by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


NEG_INF = -1e30


def online_softmax_step(q, k, v, live, acc_ref, m_ref, l_ref, scale):
    """Fold one kv block into the running (acc, m, l) VMEM scratch.

    q (R, D), k/v (Bk, D) f32; live (R, Bk) bool marks visible keys. Dead
    keys get p == 0, so a row whose keys are all dead keeps its (m, l)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale                                               # (R, Bk)
    s = jnp.where(live, s, NEG_INF)
    m_prev = m_ref[...]                                     # (R, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(live, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)


def softmax_scratch(rows, D):
    """VMEM scratch carried across the sequential kv grid dimension."""
    from jax.experimental.pallas import tpu as pltpu

    return [
        pltpu.VMEM((rows, D), jnp.float32),   # output accumulator
        pltpu.VMEM((rows, 1), jnp.float32),   # running max
        pltpu.VMEM((rows, 1), jnp.float32),   # running denominator
    ]


def init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def finish(acc_ref, l_ref):
    """acc / l, with fully-masked rows (l == 0, acc == 0) emitting zeros."""
    l = l_ref[...]
    return acc_ref[...] / jnp.where(l == 0.0, 1.0, l)


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, causal, sq, skv, block_q, block_k, num_kv):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                 # (Bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                 # (Bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        live = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            ) + (skv - sq)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            live = qpos >= kpos
        online_softmax_step(q, k, v, live, acc_ref, m_ref, l_ref, scale)

    if causal:
        # Block-level causal skip: a kv block whose first key position lies
        # strictly beyond this q block's last query is fully masked, so it
        # contributes nothing — pl.when drops its matmuls/iota entirely
        # (~2x fewer FLOPs on square causal prefill).
        q_max = i * block_q + block_q - 1 + (skv - sq)
        pl.when(q_max >= j * block_k)(_body)
    else:
        _body()

    @pl.when(j == num_kv - 1)
    def _finish():
        o_ref[0, 0] = finish(acc_ref, l_ref).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, scale: float | None = None,
    block_q: int = 128, block_k: int = 128, interpret: bool = False,
):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    num_kv = Skv // bk
    grid = (B, Hq, Sq // bq, num_kv)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, sq=Sq, skv=Skv,
        block_q=bq, block_k=bk, num_kv=num_kv,
    )
    heads_first = lambda x: jnp.swapaxes(x, 1, 2)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=softmax_scratch(bq, D),
        interpret=interpret,
    )(heads_first(q), heads_first(k), heads_first(v))
    return heads_first(out)
