"""GQA attention with RoPE/M-RoPE, optional QKV bias, prefill/decode caches.

Prefill and train use the flash kernel on TPU (chunked-jnp oracle
elsewhere); decode attends one token against a (possibly sequence-sharded)
KV cache, passing per-slot cache lengths through to the split-KV
flash-decode kernel (ops.attention with `lengths`; masked-window oracle
off-TPU) instead of materializing a dense mask.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops

from .config import ModelConfig
from .layers import apply_mrope, apply_rope, dense_init


def attn_init(key, cfg: ModelConfig):
    D, Hq, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, Hq * hd)),
        "wk": dense_init(ks[1], (D, Hkv * hd)),
        "wv": dense_init(ks[2], (D, Hkv * hd)),
        "wo": dense_init(ks[3], (Hq * hd, D)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hd,), jnp.float32)
    return p


def _rope(cfg: ModelConfig, x, positions):
    if cfg.mrope:
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return apply_rope(x, positions, cfg.rope_theta)


def attn_fwd(
    p,
    x,                       # (B, S, D)
    positions,               # (B, S) or (B, S, 3) for mrope
    cfg: ModelConfig,
    cache: Optional[dict] = None,   # {"k","v"}: (B, S_max, Hkv, hd), or
                                    # paged pools (NB, Hkv, Bs, hd)
    cache_len=None,          # i32 scalar: valid entries in cache
    mode: str = "train",     # train | prefill | decode
    block_tables=None,       # (B, max_blocks) i32: decode against paged
                             # pools instead of a contiguous cache
) -> Tuple[jax.Array, Optional[dict]]:
    B, S, D = x.shape
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = x.dtype

    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    from repro.dist.sharding import shard_act

    q = shard_act(q.reshape(B, S, Hq, hd), "batch", "seq", "heads", "none")
    k = shard_act(k.reshape(B, S, Hkv, hd), "batch", "seq", "heads", "none")
    v = shard_act(v.reshape(B, S, Hkv, hd), "batch", "seq", "heads", "none")
    q = _rope(cfg, q, positions)
    k = _rope(cfg, k, positions)

    if mode == "decode":
        assert cache is not None and S == 1
        # cache_len: scalar (whole-batch decode) or (B,) per-slot lengths
        # with -1 marking inactive serving slots (writes dropped, state
        # untouched).
        lens = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
        window = jnp.where(lens >= 0, lens + 1, 0)
        if block_tables is not None:
            # Paged cache: token at logical position lens[b] lands in
            # physical block block_tables[b, lens[b] // Bs] at offset
            # lens[b] % Bs. The scheduler guarantees that block is
            # allocated and exclusively owned (COW resolved); idle slots
            # write out-of-bounds and are dropped.
            NB, Bs = cache["k"].shape[0], cache["k"].shape[2]
            bt = jnp.asarray(block_tables, jnp.int32)
            pos = jnp.maximum(lens, 0)
            phys = jnp.take_along_axis(
                bt, (pos // Bs)[:, None], axis=1
            )[:, 0]
            phys = jnp.where(lens >= 0, phys, NB)   # OOB => dropped
            off = pos % Bs
            ck = cache["k"].at[phys, :, off].set(
                k[:, 0].astype(cache["k"].dtype), mode="drop"
            )
            cv = cache["v"].at[phys, :, off].set(
                v[:, 0].astype(cache["v"].dtype), mode="drop"
            )
            o = ops.attention(
                q, ck.astype(dt), cv.astype(dt), causal=False,
                impl=cfg.decode_impl, lengths=window, block_tables=bt,
            ).astype(dt)
            new_cache = {"k": ck, "v": cv}
        else:
            S_max = cache["k"].shape[1]
            widx = jnp.where(lens >= 0, lens, S_max)  # OOB => dropped
            brow = jnp.arange(B)
            ck = cache["k"].at[brow, widx].set(
                k[:, 0].astype(cache["k"].dtype), mode="drop"
            )
            cv = cache["v"].at[brow, widx].set(
                v[:, 0].astype(cache["v"].dtype), mode="drop"
            )
            # Cache lengths flow through as-is (no dense mask materialized
            # here): visible window = cache_len entries + the token just
            # written; idle slots (-1) get an empty window, a dead output.
            o = ops.attention(
                q, ck.astype(dt), cv.astype(dt), causal=False,
                impl=cfg.decode_impl, lengths=window,
            ).astype(dt)
            new_cache = {"k": ck, "v": cv}
    elif mode == "prefill" and block_tables is not None:
        # Chunked prefill against the paged pool: this call holds tokens
        # [start, start + S) of the sequence; KV for [0, start) already
        # sits in pool blocks (earlier chunks or a prefix-cache hit).
        # Write the chunk's k/v through the block table, then attend over
        # the whole window with causal-by-absolute-position masking
        # (kernels paged_prefill / ref.paged_prefill_ref).
        assert cache is not None
        Bs = cache["k"].shape[2]
        bt = jnp.asarray(block_tables, jnp.int32)
        start = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (B,))
        pos = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        phys = jnp.take_along_axis(bt, pos // Bs, axis=1)        # (B, S)
        off = pos % Bs
        ck = cache["k"].at[phys, :, off].set(
            k.astype(cache["k"].dtype), mode="drop"
        )
        cv = cache["v"].at[phys, :, off].set(
            v.astype(cache["v"].dtype), mode="drop"
        )
        o = ops.attention(
            q, ck.astype(dt), cv.astype(dt), causal=True,
            impl=cfg.decode_impl, lengths=start + S, block_tables=bt,
            q_offset=start,
        ).astype(dt)
        new_cache = {"k": ck, "v": cv}
    else:
        import os

        o = ops.attention(
            q, k, v, causal=True, impl=cfg.attn_impl,
            block_q=int(os.environ.get("REPRO_ATTN_BLOCK", "512")),
        )
        new_cache = None
        if mode == "prefill" and cache is not None:
            # Write the prompt's k/v into the (larger) cache at cache_len
            # (chunk 0 in practice); prompt length S <= cache size.
            off = cache_len if cache_len is not None else jnp.int32(0)
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, off, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, off, 0, 0)
            )
            new_cache = {"k": ck, "v": cv}
    y = o.reshape(B, S, Hq * hd) @ p["wo"].astype(dt)
    return y, new_cache
