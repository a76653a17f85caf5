"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each kernel in this package has exactly one oracle here; kernel tests sweep
shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.problems.uts import child_hash, child_count


# ----------------------------------------------------------- uts_expand
def uts_expand_ref(d0, d1, base, thresholds, width: int, max_depth_ok=None):
    """Expand a block of M UTS nodes: child descriptors + geometric child
    counts for `width` consecutive child indices starting at `base`.

    d0, d1: (M,) uint32 parent descriptors; base: (M,) i32.
    Returns cd0, cd1 (M, width) uint32 and m (M, width) i32 (count BEFORE the
    depth cut-off is applied — the caller owns depth logic)."""
    idx = base[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    cd0, cd1 = child_hash(d0[:, None], d1[:, None], idx, jnp)
    m = child_count(cd0, thresholds, jnp)
    return cd0, cd1, m


# ------------------------------------------------------ flash_attention
def attention_ref(q, k, v, causal: bool = True, scale: float | None = None):
    """Plain softmax attention with GQA; q (B,Sq,Hq,D), k/v (B,Skv,Hkv,D)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    kx = jnp.repeat(k, group, axis=2)
    vx = jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kx.astype(jnp.float32)) * scale
    if causal:
        # decode layout: query i sits at absolute position Skv - Sq + i
        qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
        kpos = jnp.arange(Skv)[None, :]
        logits = jnp.where(qpos >= kpos, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vx.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_ref(q, k, v, lengths, scale: float | None = None):
    """Sq==1 attention against a padded KV cache (the flash_decode oracle):
    mask = kpos < length, so row b matches attention_ref on k[b, :length].
    Rows with length == 0 are idle serving slots — the fully-masked softmax
    degenerates to uniform probs and callers ignore the output.

    q (B,1,Hq,hd); k, v (B,S,Hkv,hd); lengths (B,) i32. f32 softmax."""
    B, S, Hkv, hd = k.shape
    Hq = q.shape[2]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, hd)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bgqd,bsgd->bgqs", qf, kf) * scale        # (B,Hkv,grp,S)
    mask = jnp.arange(S)[None, :] < lens[:, None]            # (B, S)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqs,bsgd->bgqd", p, v.astype(jnp.float32))
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)


def paged_decode_ref(q, k_pool, v_pool, block_tables, lengths, layer,
                     scale: float | None = None):
    """Sq==1 attention against a block-table-indirected KV pool (the
    paged_decode oracle): gather each sequence's blocks of pool layer
    ``layer`` into their logical order, then decode_ref with the same
    window mask. Garbage table entries past the allocation are clamped
    into the pool — the window mask keeps their rows invisible.

    q (B,1,Hq,hd); k_pool/v_pool (layers, num_blocks, Hkv, Bs, hd);
    block_tables (B, max_blocks) i32; lengths (B,) i32; layer i32."""
    k = gather_pool(k_pool, block_tables, layer)
    v = gather_pool(v_pool, block_tables, layer)
    return decode_ref(q, k, v, lengths, scale=scale)


def gather_pool(pool, block_tables, layer):
    """Each sequence's blocks of pool layer ``layer`` in logical order as
    a contiguous (B, max_blocks * Bs, Hkv, hd) cache, in one gather;
    pool (layers, num_blocks, Hkv, Bs, hd). Garbage table entries are
    clamped into the pool."""
    _, NB, Hkv, Bs, hd = pool.shape
    bt = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, NB - 1)
    blocks = jnp.swapaxes(pool[layer, bt], 2, 3)  # (B, blocks, Bs, Hkv, hd)
    return blocks.reshape(bt.shape[0], -1, Hkv, hd)


def paged_prefill_ref(q, k_pool, v_pool, block_tables, lengths, q_offset,
                      scale: float | None = None):
    """Chunked-prefill attention against the paged pool (the
    paged_prefill oracle): queries are tokens ``[s, s + Sq)`` of a
    sequence whose KV for ``[0, s + Sq)`` already sits in pool blocks
    (earlier chunks / a prefix-cache hit, plus this chunk's own rows,
    written by the caller before attending). Causal: query ``i`` sees
    key positions ``<= q_offset + i``, additionally clamped to the
    ``lengths`` window so bucket-padded tail queries read no stale rows.

    q (B,Sq,Hq,hd); k_pool/v_pool (num_blocks, Hkv, Bs, hd);
    block_tables (B, max_blocks) i32; lengths (B,) i32 visible window
    (= q_offset + true chunk length); q_offset (B,) i32 chunk start."""
    B, Sq, Hq, hd = q.shape
    Hkv = k_pool.shape[1]
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    k = gather_pool(k_pool[None], block_tables, 0)   # logical order
    v = gather_pool(v_pool[None], block_tables, 0)
    S = k.shape[1]
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    offs = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    kx = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vx = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        kx) * scale
    qpos = offs[:, None] + jnp.arange(Sq, dtype=jnp.int32)[None, :]  # (B,Sq)
    kpos = jnp.arange(S, dtype=jnp.int32)
    mask = (kpos[None, None, :] <= qpos[:, :, None]) & (
        kpos[None, None, :] < lens[:, None, None]
    )                                                                # (B,Sq,S)
    logits = jnp.where(mask[:, None, :, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vx)
    return out.astype(q.dtype)


def attention_chunked(q, k, v, causal: bool = True, scale: float | None = None,
                      block_q: int = 512):
    """Memory-bounded attention: lax.map over q blocks, full kv per block
    (scores (B,H,Bq,Skv) transient instead of (B,H,Sq,Skv)). Each block is
    jax.checkpoint-ed so the BACKWARD also recomputes per-block probs (the
    flash-backward pattern) instead of saving (B,H,Sq,Skv). GQA contracts
    against the raw (B,S,Hkv,D) kv — no repeated-kv materialization.

    NOTE for roofline: XLA cost_analysis counts the q-block loop body once —
    analysis code adds the analytic correction (launch/dryrun.py)."""
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    bq = min(block_q, Sq)
    assert Sq % bq == 0, (Sq, bq)
    nblk = Sq // bq
    kpos = jnp.arange(Skv)[None, :]

    @jax.checkpoint
    def one_block(qb, i):
        qg = qb.reshape(B, bq, Hkv, group, D).astype(jnp.float32)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            k.astype(jnp.float32)) * scale
        if causal:
            qpos = (i * bq + jnp.arange(bq))[:, None] + (Skv - Sq)
            logits = jnp.where(qpos >= kpos, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return o.reshape(B, bq, Hq, D).astype(q.dtype)

    def body(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        return one_block(qb, i)

    blocks = jax.lax.map(body, jnp.arange(nblk))        # (nblk,B,bq,H,D)
    return jnp.moveaxis(blocks, 0, 1).reshape(B, Sq, Hq, D)


# ----------------------------------------------------------- mamba2_ssd
def ssd_ref(x, dt, A, B, C, h0=None):
    """Sequential state-space scan — the Mamba2 SSD semantics.

    x:  (Bt, T, H, P)   inputs per head
    dt: (Bt, T, H)      positive step sizes
    A:  (H,)            negative decay rates
    B:  (Bt, T, N)      input projections (single group)
    C:  (Bt, T, N)      output projections
    h0: optional (Bt, H, N, P) initial state
    Returns y (Bt, T, H, P), h_final (Bt, H, N, P). All math f32."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(h, t):
        a = jnp.exp(Af[None, :] * dtf[:, t])                # (Bt, H)
        dx = dtf[:, t, :, None] * xf[:, t]                  # (Bt, H, P)
        upd = Bf[:, t, None, :, None] * dx[:, :, None, :]   # (Bt, H, N, P)
        h = h * a[:, :, None, None] + upd
        y = jnp.einsum("bn,bhnp->bhp", Cf[:, t], h)         # (Bt, H, P)
        return h, y

    h = (jnp.zeros((Bt, H, N, P), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))
    h, ys = jax.lax.scan(step, h, jnp.arange(T))
    y = jnp.moveaxis(ys, 0, 1)  # (Bt, T, H, P)
    return y.astype(x.dtype), h


def ssd_chunked_ref(x, dt, A, B, C, chunk: int = 256):
    """Chunk-matmul SSD (same math as the Pallas kernel, pure jnp): scan
    over T/chunk chunks, matmuls inside. This is the form the dry-run
    compiles for long sequences (the sequential scan would be a T-trip
    while loop). Matches ssd_ref to fp tolerance."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    L = min(chunk, T)
    assert T % L == 0
    nck = T // L
    xf = x.astype(jnp.float32).reshape(Bt, nck, L, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bt, nck, L, H)
    Bf = B.astype(jnp.float32).reshape(Bt, nck, L, N)
    Cf = C.astype(jnp.float32).reshape(Bt, nck, L, N)
    Af = A.astype(jnp.float32)
    tri = jnp.tril(jnp.ones((L, L), jnp.float32))

    def step(h, ck):
        xc, dtc, Bc, Cc = ck                       # (Bt,L,H,P),(Bt,L,H),...
        s = Af[None, None, :] * jnp.cumsum(dtc, axis=1)      # (Bt,L,H)
        dx = dtc[..., None] * xc                             # (Bt,L,H,P)
        G = jnp.einsum("btn,bun->btu", Cc, Bc)               # (Bt,L,L)
        logm = s[:, :, None] - s[:, None, :]                 # (Bt,L,L,H)
        M = jnp.exp(jnp.minimum(logm, 0.0)) * tri[None, :, :, None]
        y = jnp.einsum("btu,btuh,buhp->bthp", G, M, dx)
        y = y + jnp.exp(s)[..., None] * jnp.einsum(
            "btn,bhnp->bthp", Cc, h
        )
        s_l = s[:, -1]                                       # (Bt,H)
        wts = jnp.exp(s_l[:, None] - s)[..., None] * dx      # (Bt,L,H,P)
        h = jnp.exp(s_l)[:, :, None, None] * h + jnp.einsum(
            "bun,buhp->bhnp", Bc, wts
        )
        return h, y

    h0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    h, ys = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
         jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(Bt, T, H, P)
    return y.astype(x.dtype), h


# -------------------------------------------------------------- moe_gmm
def gmm_ref(x, w, group_sizes):
    """Grouped matmul: rows of x are sorted by expert; group_sizes (E,) give
    each expert's row count. out[i] = x[i] @ w[expert_of(i)]."""
    T, D = x.shape
    E, _, F = w.shape
    bounds = jnp.cumsum(group_sizes)
    expert_of = jnp.searchsorted(bounds, jnp.arange(T), side="right")
    expert_of = jnp.clip(expert_of, 0, E - 1)
    return jnp.einsum(
        "td,tdf->tf", x.astype(jnp.float32),
        w.astype(jnp.float32)[expert_of],
    ).astype(x.dtype)
