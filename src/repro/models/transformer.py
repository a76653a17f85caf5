"""Decoder-only LM assembly for all assigned families.

Families compose from blocks:
  dense/vlm/audio : [rmsnorm -> GQA attn -> rmsnorm -> SwiGLU] x L
  moe             : [rmsnorm -> GQA attn -> rmsnorm -> MoE] x L
  ssm             : [rmsnorm -> Mamba2] x L
  hybrid (zamba2) : [rmsnorm -> Mamba2] x L, plus ONE weight-shared
                    (attn + MLP) block applied every `attn_every` layers
                    (Zamba2's shared-block weight tying)

Layers are scanned (stacked params, O(1) HLO in depth — compile time matters
at 512 devices) with a configurable remat policy. Params are stored float32
(master copies); compute casts to cfg.dtype.

Modality frontends are stubs per spec: musicgen consumes EnCodec token
streams (B,S,K) with K embedding tables + K output heads; qwen2-vl consumes
precomputed merged embeddings (B,S,D) plus M-RoPE positions (B,S,3).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .attention import attn_fwd, attn_init
from .config import ModelConfig
from .layers import embed_init, mlp_fwd, mlp_init, rmsnorm
from .mamba2 import mamba_fwd, mamba_init
from .moe import moe_fwd, moe_init


# ------------------------------------------------------------------ init
def _block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":
        return {"ln": jnp.zeros((cfg.d_model,)), "mamba": mamba_init(ks[0], cfg)}
    if cfg.family == "hybrid":
        return {"ln": jnp.zeros((cfg.d_model,)), "mamba": mamba_init(ks[0], cfg)}
    blk = {
        "ln1": jnp.zeros((cfg.d_model,)),
        "attn": attn_init(ks[0], cfg),
        "ln2": jnp.zeros((cfg.d_model,)),
    }
    if cfg.family == "moe":
        blk["moe"] = moe_init(ks[1], cfg)
    else:
        blk["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff)
    return blk


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    Vp, D = cfg.padded_vocab, cfg.d_model
    params: Dict[str, Any] = {}
    if cfg.n_codebooks:
        params["embed"] = embed_init(ks[0], (cfg.n_codebooks, Vp, D))
        if not cfg.tie_embeddings:
            params["head"] = embed_init(ks[1], (cfg.n_codebooks, D, Vp))
    else:
        params["embed"] = embed_init(ks[0], (Vp, D))
        if not cfg.tie_embeddings:
            params["head"] = embed_init(ks[1], (D, Vp))
    layer_keys = jax.random.split(ks[2], cfg.n_layers)
    params["blocks"] = jax.vmap(lambda k: _block_init(k, cfg))(layer_keys)
    if cfg.family == "hybrid":
        params["shared"] = {
            "ln1": jnp.zeros((D,)),
            "attn": attn_init(ks[3], cfg),
            "ln2": jnp.zeros((D,)),
            "mlp": mlp_init(ks[4], D, cfg.d_ff),
        }
    params["final_ln"] = jnp.zeros((D,))
    return params


# ----------------------------------------------------------------- cache
def n_attn_caches(cfg: ModelConfig) -> int:
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def make_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    cache: Dict[str, Any] = {}
    na = n_attn_caches(cfg)
    if na:
        kv = (na, batch, max_seq, cfg.n_kv_heads, cfg.hd)
        cache["k"] = jnp.zeros(kv, dtype)
        cache["v"] = jnp.zeros(kv, dtype)
    if cfg.family in ("ssm", "hybrid"):
        L = cfg.n_layers
        k1 = cfg.ssm_conv - 1
        cache["conv_x"] = jnp.zeros((L, batch, k1, cfg.d_inner), dtype)
        cache["conv_B"] = jnp.zeros((L, batch, k1, cfg.ssm_state), dtype)
        cache["conv_C"] = jnp.zeros((L, batch, k1, cfg.ssm_state), dtype)
        cache["ssm"] = jnp.zeros(
            (L, batch, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim),
            jnp.float32,
        )
    return cache


def make_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     slots: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Paged serving cache: attention k/v are flat per-layer *pools* of
    ``num_blocks`` blocks of ``block_size`` tokens, shared by every
    sequence and indirected through per-sequence block tables
    (serve/kvpool.py owns the mapping). Each block is head-major,
    (n_kv_heads, block_size, hd), the layout the paged kernels read.
    Recurrent conv/ssm state is O(1) per sequence, so it stays dense per
    decode slot."""
    cache: Dict[str, Any] = {}
    na = n_attn_caches(cfg)
    if na:
        kv = (na, num_blocks, cfg.n_kv_heads, block_size, cfg.hd)
        cache["k"] = jnp.zeros(kv, dtype)
        cache["v"] = jnp.zeros(kv, dtype)
    if cfg.family in ("ssm", "hybrid"):
        L = cfg.n_layers
        k1 = cfg.ssm_conv - 1
        cache["conv_x"] = jnp.zeros((L, slots, k1, cfg.d_inner), dtype)
        cache["conv_B"] = jnp.zeros((L, slots, k1, cfg.ssm_state), dtype)
        cache["conv_C"] = jnp.zeros((L, slots, k1, cfg.ssm_state), dtype)
        cache["ssm"] = jnp.zeros(
            (L, slots, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_headdim),
            jnp.float32,
        )
    return cache


def _slice_cache(cache, keys, idx):
    return {
        k.split("/")[-1]: jax.lax.dynamic_index_in_dim(cache[k], idx, 0, False)
        for k in keys
    }


def _update_cache(cache, keys, idx, new):
    out = dict(cache)
    for k in keys:
        leaf = new[k.split("/")[-1]]
        out[k] = jax.lax.dynamic_update_index_in_dim(
            cache[k], leaf.astype(cache[k].dtype), idx, 0
        )
    return out


# --------------------------------------------------------------- blocks
def _apply_shared_block(cfg, sp, x, positions, cache, app_idx, cache_len,
                        mode, block_tables=None):
    """Zamba2's weight-shared attention+MLP block."""
    h, new_kv = attn_fwd(
        sp["attn"], rmsnorm(x, sp["ln1"], cfg.norm_eps), positions, cfg,
        cache=None if not cache else _slice_cache(cache, ("k", "v"), app_idx),
        cache_len=cache_len, mode=mode, block_tables=block_tables,
    )
    x = x + h
    x = x + mlp_fwd(sp["mlp"], rmsnorm(x, sp["ln2"], cfg.norm_eps), x.dtype)
    if cache and new_kv is not None:
        cache = _update_cache(cache, ("k", "v"), app_idx, new_kv)
    return x, cache


def _apply_block(cfg, bp, shared, li, x, positions, cache, cache_len, mode,
                 block_tables=None):
    """One scanned layer. Returns (x, cache, aux)."""
    aux = _zero_aux(cfg)
    active = None
    if mode == "decode" and cache_len is not None:
        cl = jnp.asarray(cache_len)
        if cl.ndim == 1:
            active = cl >= 0
    if cfg.family in ("ssm", "hybrid"):
        mcache = (
            _slice_cache(cache, ("conv_x", "conv_B", "conv_C", "ssm"), li) if cache else None
        )
        h, new_m = mamba_fwd(
            bp["mamba"], rmsnorm(x, bp["ln"], cfg.norm_eps), cfg,
            cache=mcache, mode=mode, active=active,
        )
        x = x + h
        if cache and new_m is not None:
            cache = _update_cache(cache, ("conv_x", "conv_B", "conv_C", "ssm"), li, new_m)
        if cfg.family == "hybrid":
            is_app = (li + 1) % cfg.attn_every == 0
            app_idx = (li + 1) // cfg.attn_every - 1

            def yes(args):
                x, cache = args
                return _apply_shared_block(
                    cfg, shared, x, positions, cache, app_idx, cache_len,
                    mode, block_tables
                )

            x, cache = jax.lax.cond(is_app, yes, lambda a: a, (x, cache))
        return x, cache, aux

    acache = _slice_cache(cache, ("k", "v"), li) if cache else None
    h, new_kv = attn_fwd(
        bp["attn"], rmsnorm(x, bp["ln1"], cfg.norm_eps), positions, cfg,
        cache=acache, cache_len=cache_len, mode=mode,
        block_tables=block_tables,
    )
    x = x + h
    hin = rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        h, moe_aux = moe_fwd(bp["moe"], hin, cfg)
        aux = {"aux_loss": moe_aux["aux_loss"],
               "expert_counts": moe_aux["expert_counts"],
               "dropped": moe_aux["dropped"]}
    else:
        h = mlp_fwd(bp["mlp"], hin, x.dtype)
    x = x + h
    if cache and new_kv is not None:
        cache = _update_cache(cache, ("k", "v"), li, new_kv)
    return x, cache, aux


def _zero_aux(cfg: ModelConfig):
    if cfg.family == "moe":
        return {
            "aux_loss": jnp.zeros((), jnp.float32),
            "expert_counts": jnp.zeros((cfg.n_experts,), jnp.float32),
            "dropped": jnp.zeros((), jnp.float32),
        }
    return {"aux_loss": jnp.zeros((), jnp.float32)}


# -------------------------------------------------------------- forward
def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # "full": save nothing


def run_layers(params, cfg: ModelConfig, x, positions, cache, cache_len,
               mode, block_tables=None):
    from repro.dist.sharding import shard_act

    shared = params.get("shared")

    def body(carry, xs):
        x, cache, aux_acc = carry
        bp, li = xs
        x, cache, aux = _apply_block(
            cfg, bp, shared, li, x, positions, cache, cache_len, mode,
            block_tables
        )
        x = shard_act(x, "batch", "seq", "act_embed")
        aux_acc = jax.tree.map(lambda a, b: a + b, aux_acc, aux)
        return (x, cache, aux_acc), None

    body = _remat(body, cfg)
    idxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    cache = cache if cache else {}
    if cfg.scan_layers:
        (x, cache, aux), _ = jax.lax.scan(
            body, (x, cache, _zero_aux(cfg)), (params["blocks"], idxs)
        )
    else:
        carry = (x, cache, _zero_aux(cfg))
        for i in range(cfg.n_layers):
            bp = jax.tree.map(lambda a: a[i], params["blocks"])
            carry, _ = body(carry, (bp, idxs[i]))
        x, cache, aux = carry
    return x, cache, aux


def embed_tokens(params, cfg: ModelConfig, tokens):
    dt = jnp.dtype(cfg.dtype)
    if cfg.n_codebooks:
        # musicgen: sum the K codebook embeddings (B,S,K) -> (B,S,D)
        embs = params["embed"].astype(dt)          # (K, Vp, D)
        x = sum(
            embs[k][tokens[..., k]] for k in range(cfg.n_codebooks)
        )
        return x
    return params["embed"].astype(dt)[tokens]


def lm_logits(params, cfg: ModelConfig, x):
    dt = x.dtype
    if cfg.n_codebooks:
        head = (
            jnp.swapaxes(params["embed"], 1, 2)
            if cfg.tie_embeddings else params["head"]
        )                                           # (K, D, Vp)
        return jnp.einsum("bsd,kdv->bskv", x, head.astype(dt))
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ head.astype(dt)


def forward(
    params,
    cfg: ModelConfig,
    tokens=None,              # (B,S) i32, or (B,S,K) for audio
    embeds=None,              # (B,S,D) for vlm (frontend stub output)
    positions=None,           # (B,S) or (B,S,3); default arange
    cache: Optional[dict] = None,
    cache_len=None,
    mode: str = "train",
    block_tables=None,        # (B, max_blocks) i32: paged decode cache
):
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
        B, S = x.shape[:2]
    else:
        x = embed_tokens(params, cfg, tokens)
        B, S = tokens.shape[:2]
    if positions is None:
        if cache_len is None:
            off = jnp.zeros((B, 1), jnp.int32)
        else:
            cl = jnp.asarray(cache_len, jnp.int32)
            off = (jnp.maximum(cl, 0)[:, None] if cl.ndim == 1
                   else jnp.broadcast_to(cl, (B, 1)))
        positions = jnp.arange(S, dtype=jnp.int32)[None] + off
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (B, S, 3))
    x, cache, aux = run_layers(params, cfg, x, positions, cache, cache_len,
                               mode, block_tables)
    x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    return logits, cache, aux
