"""Paged split-KV Pallas flash-decode kernel: one query token against a
block-table-indirected KV pool (Sq == 1, the paged serving hot path) —
plus ``paged_prefill``, the same block walk for a *chunk* of Sq query
tokens at offset ``q_offset`` (chunked prefill: tokens ``[s, e)``
attending causally to pool blocks ``[0, e)``).

flash_decode.py streams a *contiguous* per-slot cache; here the cache is
a flat pool of KV blocks shared by every sequence (serve/kvpool.py) and
each sequence names its blocks through a block table. Two scalar-prefetch
operands — ``block_tables`` (B, max_blocks) i32 and ``lengths`` (B,) i32
— arrive before the kernel body runs, so the k/v **index maps walk the
table**: grid step ``(b, h, j)`` fetches physical block
``block_tables[b, min(j, last_live(b))]`` instead of row-range
``[j*bk, (j+1)*bk)`` of a dense cache. The same three-level gating as
the contiguous kernel applies:

  * DMA clamp   — past-window grid steps clamp the *logical* block index
    to the last live one; the table lookup then repeats the same physical
    block, the pipeline sees an unchanged index and issues no DMA — dead
    blocks are never fetched;
  * block skip  — ``pl.when`` drops compute for blocks at or past the
    window (idle slots, window == 0, skip everything and emit zeros);
  * lane mask   — the partial tail block masks key positions >= window.

The pool is head-major, (num_blocks, Hkv, Bs, D), so one grid step reads
a (Bs, D) block of one kv head — a shape the TPU compiler tiles for any
Bs and D. The KV block size is the pool's block size (one pool block per
grid step), chosen by ``core.autotune.paged_block_kv``. Decode runs the
contiguous kernel's body (flash_decode.decode_kernel): each step takes the
``group`` query heads of one kv head as a (group, D) block, so the grid
is (batch, kv_heads, max_blocks). Oracle: ``ref.paged_decode_ref``
(gather blocks -> decode_ref). Routed via
``ops.attention(..., block_tables=...)``.

``paged_prefill`` generalizes the walk to an (Sq, D) query block of one
query head (grid (batch, q_heads, max_blocks), kv head h // group) and a
third scalar-prefetch operand ``q_offset`` ((B,) i32 chunk start): the
mask becomes causal-by-absolute-position (``kpos <= q_offset + i``)
intersected with the ``lengths`` window; table walk, DMA clamp, block
skip and lane mask are unchanged. Oracle: ``ref.paged_prefill_ref``;
routed via ``ops.attention(..., block_tables=..., q_offset=...)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import (finish, init_scratch, online_softmax_step,
                              softmax_scratch)
from .flash_decode import decode_kernel


def _walk(bt, lens, b, j, Bs, NB):
    """Physical pool block for grid step j of slot b. Past-window logical
    blocks clamp to the last live one so the physical index repeats (no
    DMA, compute skipped by pl.when); unallocated/garbage table entries
    are clamped into the pool so the address is always valid."""
    last = jnp.maximum(lens[b] - 1, 0) // Bs
    return jnp.clip(bt[b, jnp.minimum(j, last)], 0, NB - 1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode(q, k_pool, v_pool, block_tables, lengths, *,
                 scale: float | None = None, interpret: bool = False):
    """q: (B, 1, Hq, D); k_pool, v_pool: (num_blocks, Hkv, Bs, D) flat
    block pools; block_tables: (B, max_blocks) i32 physical block per
    logical block (entries past the allocation may be any value — they
    are clamped away); lengths: (B,) i32 visible window (0 => idle slot,
    output zeros). Returns (B, 1, Hq, D)."""
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hq, D = q.shape
    NB, Hkv, Bs, _ = k_pool.shape
    assert Sq == 1, f"paged_decode is Sq==1 only, got {Sq}"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    max_blocks = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(D)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    def kv_map(b, h, j, bt, lens):
        return (_walk(bt, lens, b, j, Bs, NB), h, 0, 0)

    q_map = lambda b, h, j, bt, lens: (b, h, 0, 0)
    kernel = functools.partial(
        decode_kernel, scale=scale, block_k=Bs, num_kv=max_blocks
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), q_map),
            pl.BlockSpec((1, 1, Bs, D), kv_map),
            pl.BlockSpec((1, 1, Bs, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), q_map),
        scratch_shapes=softmax_scratch(group, D),
    )
    out = pl.pallas_call(
        lambda bt_ref, *refs: kernel(*refs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=interpret,
    )(bt, lens, q.reshape(B, Hkv, group, D), k_pool, v_pool)
    return out.reshape(B, 1, Hq, D)


def _paged_prefill_kernel(bt_ref, len_ref, off_ref, q_ref, k_ref, v_ref,
                          o_ref, acc_ref, m_ref, l_ref, *, scale,
                          block_size, max_blocks, sq):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    n = len_ref[b]      # visible window: q_offset + true chunk length
    off = off_ref[b]    # absolute position of query row 0

    @pl.when(j * block_size < n)  # skip blocks wholly past the window
    def _body():
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (sq, block_size), 1
        )
        qpos = off + jax.lax.broadcasted_iota(
            jnp.int32, (sq, block_size), 0
        )
        # Causal by absolute position, clamped to the window; a kv block
        # entirely after some query row leaves that row's lane mask all
        # dead, so its (m, l) stay untouched.
        online_softmax_step(
            q_ref[0, 0].astype(jnp.float32),
            k_ref[0, 0].astype(jnp.float32),
            v_ref[0, 0].astype(jnp.float32),
            (kpos <= qpos) & (kpos < n), acc_ref, m_ref, l_ref, scale,
        )

    @pl.when(j == max_blocks - 1)
    def _finish():
        o_ref[0, 0] = finish(acc_ref, l_ref).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill(q, k_pool, v_pool, block_tables, lengths, q_offset, *,
                  scale: float | None = None, interpret: bool = False):
    """Chunked-prefill attention against the paged pool: q (B, Sq, Hq, D)
    holds the chunk's Sq query tokens whose absolute positions start at
    ``q_offset`` ((B,) i32); k_pool/v_pool/(B, max_blocks) block_tables
    as in paged_decode; lengths (B,) i32 is the visible window
    ``q_offset + true_chunk_len`` (bucket-padded tail queries emit
    garbage the caller discards). Returns (B, Sq, Hq, D)."""
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hq, D = q.shape
    NB, Hkv, Bs, _ = k_pool.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    max_blocks = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(D)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))

    def kv_map(b, h, j, bt, lens, offs):
        return (_walk(bt, lens, b, j, Bs, NB), h // group, 0, 0)

    q_map = lambda b, h, j, bt, lens, offs: (b, h, 0, 0)
    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, block_size=Bs,
        max_blocks=max_blocks, sq=Sq,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hq, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, Sq, D), q_map),
            pl.BlockSpec((1, 1, Bs, D), kv_map),
            pl.BlockSpec((1, 1, Bs, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, Sq, D), q_map),
        scratch_shapes=softmax_scratch(Sq, D),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        interpret=interpret,
    )(bt, lens, offs, jnp.swapaxes(q, 1, 2), k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)
