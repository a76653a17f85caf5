"""Paged Pallas flash-decode kernel: one query token against a
block-table-indirected KV pool (Sq == 1, the paged serving hot path) —
plus ``paged_prefill``, a one-block walk for a *chunk* of Sq query
tokens at offset ``q_offset`` (chunked prefill: tokens ``[s, e)``
attending causally to pool blocks ``[0, e)``).

flash_decode.py streams a *contiguous* per-slot cache; here the cache is
a flat pool of KV blocks ("pages") shared by every sequence
(serve/kvpool.py) and each sequence names its pages through a block
table. ``paged_decode`` reads the serving engine's stacked pool, every
layer's blocks in one array (layers, num_blocks, Hkv, Bs, D), at a layer
index, so no caller slices a layer's pool out (or casts it) around the
call. Three scalar-prefetch operands — ``block_tables`` (B, max_blocks)
i32, ``lengths`` (B,) i32 and ``layer`` (1,) i32 — arrive before the
kernel body runs.

``paged_decode`` decouples the compute block from the pool block: grid
step ``(b, j)`` of the grid ``(batch, max_blocks // P)`` handles pages
``block_tables[b, j*P : (j+1)*P]`` of slot b for every kv head. The
pools stay in HBM (``memory_space=pl.ANY``); each block is head-major,
(Hkv, Bs, D), so one async copy per page (``pool.at[layer, page]``)
carries all kv heads into a double-buffered VMEM scratch (2, P, Hkv, Bs,
D) of the pool's dtype. Each live step starts the copies of the next live
step (the same slot's next step, or step 0 of the next slot with a
window) before waiting for its own, so copies overlap compute, across
slots too. Gating:

  * dead steps — a step wholly past its slot's window copies and
    computes nothing; the last live step copies only its live pages, so
    table entries past the allocation are never read; idle slots
    (window 0) emit exact zeros;
  * lane mask  — keys at or past the window score -inf and their value
    rows are zeroed, so stale buffer rows never reach the accumulator.

Per kv head, the ``group`` query heads are the rows of the f32 online
softmax (flash_attention.online_softmax_step) against the step's P*Bs
keys. ``paged_decode_grid`` chooses P from the shapes alone: the largest
divisor of max_blocks with at most 512 tokens a step whose
double-buffered k/v pages fit a 4 MiB VMEM budget.

A head dim that is not a multiple of 128 lanes (TinyLlama 64, Zamba2
112) keeps the one-block walk: grid (batch, kv_heads, max_blocks), one
(Bs, D) block of one kv head a step through the BlockSpec pipeline
(``_walk``, flash_decode.decode_kernel), whose index map leads with the
layer. Mosaic slices a ref only in whole 128-lane tiles, so the page
copies cannot address such a pool, and padding it to the lane width
would move the whole pool every call.
Oracle: ``ref.paged_decode_ref`` (gather blocks -> decode_ref). Routed
via ``ops.attention(..., block_tables=..., layer=...)``.

``paged_prefill`` takes one layer's pool (num_blocks, Hkv, Bs, D) and
walks one pool block a grid step (``_walk``), grid (batch, q_heads,
max_blocks), with an (Sq, D) query block of one query head (kv head
h // group) and a third scalar-prefetch operand ``q_offset`` ((B,) i32
chunk start). Past-window steps clamp the logical block to the last
live one, so the physical index repeats and the pipeline issues no DMA;
``pl.when`` skips their compute; the mask is causal-by-absolute-position
(``kpos <= q_offset + i``) intersected with the ``lengths`` window.
Oracle: ``ref.paged_prefill_ref``; routed via
``ops.attention(..., block_tables=..., q_offset=...)``.
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


# Compute-step caps for paged_decode: at most this many kv tokens per
# grid step, and at most this many bytes of double-buffered k/v pages.
_STEP_TOKENS = 512
_KV_VMEM_BYTES = 4 << 20
_LANES = 128


def paged_decode_grid(max_blocks, Bs, D, Hkv, dtype):
    """How ``paged_decode`` walks one slot's block table: ``(pages,
    steps)``, the pool blocks one grid step reads and the grid steps a
    slot takes. A head dim that is a multiple of 128 lanes copies pages
    of every kv head: pages is the largest divisor of ``max_blocks`` with
    at most ``_STEP_TOKENS`` tokens whose double-buffered k and v pages
    (2 x 2 x pages x Hkv x Bs x D) fit in ``_KV_VMEM_BYTES``, at least 1,
    and steps is ``max_blocks // pages``. Any other head dim walks one
    block of one kv head a step: ``(1, Hkv * max_blocks)``."""
    if D % _LANES:
        return 1, Hkv * max_blocks
    page_bytes = Hkv * Bs * D * jnp.dtype(dtype).itemsize
    best = 1
    for p in range(2, max_blocks + 1):
        if p * Bs > _STEP_TOKENS or 4 * p * page_bytes > _KV_VMEM_BYTES:
            break
        if max_blocks % p == 0:
            best = p
    return best, max_blocks // best


def paged_decode_live_steps(windows, max_blocks, Bs, D, Hkv, dtype):
    """Grid steps of ``paged_decode`` that read pool blocks, summed over
    ``windows`` (an int array, one visible window per slot and call; 0 for
    an idle slot). Steps past a window read nothing."""
    pages, steps = paged_decode_grid(max_blocks, Bs, D, Hkv, dtype)
    heads = steps // (max_blocks // pages)   # kv heads a table entry takes
    w = np.asarray(windows, np.int64)
    return int((-(-w // (pages * Bs))).sum()) * heads


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, state, acc_ref, m_ref,
                         l_ref, *, scale, pages, num_steps):
    """Grid step (b, j): pool pages ``[j*P, (j+1)*P)`` of slot b, every kv
    head, of layer ``layer_ref[0]``. ``state`` (SMEM) holds the k/v
    buffer the step's pages land in and whether an earlier step already
    started their copies."""
    from jax.experimental.pallas import tpu as pltpu

    b, j = pl.program_id(0), pl.program_id(1)
    B = pl.num_programs(0)
    _, NB, Hkv, Bs, _ = k_hbm.shape
    layer = layer_ref[0]
    bk = pages * Bs

    def live_pages(b, j):
        return jnp.clip(pl.cdiv(len_ref[b], Bs) - j * pages, 0, pages)

    def page_copies(b, j, buf, start):
        """Start (else wait for) the copies of step (b, j)'s live pages
        into buffer ``buf``: one copy per page carries all Hkv heads."""
        def body(i, carry):
            page = jnp.clip(bt_ref[b, j * pages + i], 0, NB - 1)
            for hbm, vmem, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                copy = pltpu.make_async_copy(
                    hbm.at[layer, page], vmem.at[buf, i], sems.at[s, buf]
                )
                copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, live_pages(b, j), body, 0)

    @pl.when((b == 0) & (j == 0))
    def _reset():
        state[0] = 0
        state[1] = 0

    @pl.when(j == 0)
    def _init():
        init_scratch(acc_ref, m_ref, l_ref)

    n = len_ref[b]

    @pl.when(j * bk < n)  # steps wholly past the window copy nothing
    def _body():
        cur = state[0]

        @pl.when(state[1] == 0)  # first live step: nothing prefetched
        def _():
            page_copies(b, j, cur, start=True)

        # Prefetch the next live step, in this slot or the next slot with
        # a window, while this step computes.
        nb = jax.lax.while_loop(
            lambda c: (c < B) & (len_ref[jnp.minimum(c, B - 1)] == 0),
            lambda c: c + 1, b + 1,
        )
        same = (j + 1) * bk < n
        nxt_b = jnp.where(same, b, nb)
        has_next = nxt_b < B

        @pl.when(has_next)
        def _():
            page_copies(nxt_b, jnp.where(same, j + 1, 0), 1 - cur,
                        start=True)

        state[0] = 1 - cur
        state[1] = has_next.astype(jnp.int32)
        page_copies(b, j, cur, start=False)

        # Keys at or past the window: masked scores, and zeroed values, so
        # stale buffer rows (pages not copied this step) never reach acc.
        G = q_ref.shape[2]
        key_live = j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (G, bk), 1) < n
        row_live = j * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0) < n
        for h in range(Hkv):
            k = k_buf[cur, :, h].astype(jnp.float32).reshape(bk, -1)
            v = v_buf[cur, :, h].astype(jnp.float32).reshape(bk, -1)
            online_softmax_step(
                q_ref[0, h].astype(jnp.float32), k,
                jnp.where(row_live, v, 0.0), key_live,
                acc_ref.at[h], m_ref.at[h], l_ref.at[h], scale,
            )

    @pl.when(j == num_steps - 1)
    def _finish():
        for h in range(Hkv):
            o_ref[0, h] = finish(acc_ref.at[h], l_ref.at[h]).astype(
                o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode(q, k_pool, v_pool, block_tables, lengths, layer, *,
                 scale: float | None = None, interpret: bool = False):
    """q: (B, 1, Hq, D); k_pool, v_pool: (layers, num_blocks, Hkv, Bs, D)
    stacked block pools, any float dtype (pages are read in it and
    upcast to f32 in VMEM); block_tables: (B, max_blocks) i32 physical
    block per logical block (entries past the allocation may be any value
    — they never reach the result); lengths: (B,) i32 visible window (0
    => idle slot, output zeros); layer: i32 scalar, the pool layer read.
    A caller holding one layer's pool passes ``pool[None]`` and layer 0.
    Returns (B, 1, Hq, D)."""
    from jax.experimental.pallas import tpu as pltpu

    B, Sq, Hq, D = q.shape
    _, NB, Hkv, Bs, _ = k_pool.shape
    assert Sq == 1, f"paged_decode is Sq==1 only, got {Sq}"
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    max_blocks = block_tables.shape[1]
    scale = float(scale) if scale is not None else 1.0 / np.sqrt(D)
    bt = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    li = jnp.asarray(layer, jnp.int32).reshape(1)
    q = q.reshape(B, Hkv, group, D)
    if D % _LANES:
        out = _paged_decode_walk(q, k_pool, v_pool, bt, lens, li, scale,
                                 interpret)
        return out.reshape(B, 1, Hq, D)
    pages, num_steps = paged_decode_grid(max_blocks, Bs, D, Hkv,
                                         k_pool.dtype)

    slot_map = lambda b, j, bt, lens, li: (b, 0, 0, 0)
    page_buf = pltpu.VMEM((2, pages, Hkv, Bs, D), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, num_steps),
        in_specs=[
            pl.BlockSpec((1, Hkv, group, D), slot_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, group, D), slot_map),
        scratch_shapes=[
            page_buf, page_buf,
            pltpu.SemaphoreType.DMA((2, 2)),   # (k|v, buffer)
            pltpu.SMEM((2,), jnp.int32),       # (buffer, prefetched)
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               pages=pages, num_steps=num_steps)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        # Copies started in one step are waited for in a later one.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(bt, lens, li, q, k_pool, v_pool)
    return out.reshape(B, 1, Hq, D)


def _paged_decode_walk(q, k_pool, v_pool, bt, lens, li, scale, interpret):
    """paged_decode for head dims off the lane width: grid (B, Hkv,
    max_blocks), one (Bs, D) block of one kv head of layer ``li[0]`` a
    step, fetched by the BlockSpec pipeline along the table (``_walk``).
    q: (B, Hkv, group, D); returns the same shape."""
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, group, D = q.shape
    _, NB, _, Bs, _ = k_pool.shape
    max_blocks = bt.shape[1]

    def kv_map(b, h, j, bt, lens, li):
        return (li[0], _walk(bt, lens, b, j, Bs, NB), h, 0, 0)

    q_map = lambda b, h, j, bt, lens, li: (b, h, 0, 0)
    kernel = functools.partial(
        decode_kernel, scale=scale, block_k=Bs, num_kv=max_blocks
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, group, D), q_map),
            pl.BlockSpec((None, 1, 1, Bs, D), kv_map),
            pl.BlockSpec((None, 1, 1, Bs, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), q_map),
        scratch_shapes=softmax_scratch(group, D),
    )
    return pl.pallas_call(
        lambda bt_ref, len_ref, li_ref, *refs: kernel(len_ref, *refs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(bt, lens, li, q, k_pool, v_pool)


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
