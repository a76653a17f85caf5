"""Split-KV Pallas flash-decode kernel: one query token against a padded
KV cache, specialized for the serving hot path (Sq == 1).

The prefill kernel (flash_attention.py) tiles queries and keys; at decode
there is exactly one query token per (batch, head), so each grid step
takes the ``group`` query heads that share one kv head as a (group, D)
block: the grid becomes (batch, kv_heads, kv_blocks) with the KV dimension
innermost — TPU grids execute sequentially, so the online-softmax partials
(running max m, denominator l, weighted accumulator acc) live in VMEM
scratch across KV steps and the (group, D) output tile is written once on
the last step.

Per-slot cache lengths arrive as a scalar-prefetch operand
(PrefetchScalarGridSpec), so they gate the kernel at three levels:
  * DMA clamp   — the k/v index maps clamp past-window block indices to
    the slot's last live block; the pipeline sees an unchanged index and
    issues no new fetch, so a 70-token slot in a 4096-row bucket streams
    ~1/64th of the cache from HBM instead of all of it;
  * block skip  — ``pl.when`` drops the matmuls/softmax update for blocks
    at or past the window (idle slots, window == 0, skip every block and
    emit zeros);
  * lane mask   — the partial tail block masks key positions >= window
    before the softmax.

Layout: the kernel reads the cache head-major, (B, Hkv, S, D), so each
block ends in (block_k, D) as the TPU compiler requires; the wrapper
transposes the model's (B, S, Hkv, D) cache on the way in. ``decode_kernel``
is shared with the paged kernel (paged_decode.py), which differs only in
its index maps. Oracle: ref.attention_ref on the visible window
(ref.decode_ref is the padded-cache form). Block sizes come from
core.autotune.DECODE_BLOCK_K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import (finish, init_scratch, online_softmax_step,
                              softmax_scratch)


def decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, scale, block_k, num_kv):
    """One (slot, kv head, kv block) step: q (group, D) vs k/v (block_k,
    D). ``len_ref`` holds each slot's visible window (0 => idle)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    n = len_ref[b]

    @pl.when(j * block_k < n)  # skip past-window blocks and idle slots
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                     # (G, D)
        kpos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1
        )
        online_softmax_step(
            q, k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32), kpos < n,
            acc_ref, m_ref, l_ref, scale,
        )

    @pl.when(j == num_kv - 1)
    def _finish():
        o_ref[0, 0] = finish(acc_ref, l_ref).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret")
)
def flash_decode(q, k, v, lengths, *, scale: float | None = None,
                 block_k: int | None = None, interpret: bool = False):
    """q: (B, 1, Hq, D); k, v: (B, S, Hkv, D); lengths: (B,) i32 visible
    window per slot (0 => idle slot, output zeros). Returns (B, 1, Hq, D).
    """
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hq, D = q.shape
    _, S, Hkv, _ = k.shape
    assert Sq == 1, f"flash_decode is Sq==1 only, got {Sq}"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(D)
    if block_k is None:
        from repro.core.autotune import decode_block_k

        block_k = decode_block_k(S, D)
    bk = max(1, min(block_k, S))
    while S % bk:  # cache buckets are powers of two; keep the grid exact
        bk //= 2
    num_kv = S // bk
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    def kv_map(b, h, j, lens):
        # Clamp past-window blocks to the slot's last live block: the
        # pipeline skips the DMA for a repeated index, and pl.when skips
        # the compute, so dead cache rows are neither fetched nor read.
        last = jnp.maximum(lens[b] - 1, 0) // bk
        return (b, h, jnp.minimum(j, last), 0)

    q_map = lambda b, h, j, lens: (b, h, 0, 0)
    kernel = functools.partial(
        decode_kernel, scale=scale, block_k=bk, num_kv=num_kv
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, num_kv),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), q_map),
        scratch_shapes=softmax_scratch(group, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(lens, q.reshape(B, Hkv, group, D), jnp.swapaxes(k, 1, 2),
      jnp.swapaxes(v, 1, 2))
    return out.reshape(B, 1, Hq, D)
