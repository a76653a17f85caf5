"""Dispatch layer: jit'd public entry points that pick the Pallas kernel on
TPU and the pure-jnp oracle elsewhere (on CPU the test suite validates the
kernels in interpret mode; the models call through here, so a TPU run gets
the kernels with zero code change).
"""
from __future__ import annotations

import jax

from . import ref
from .flash_attention import flash_attention
from .flash_decode import flash_decode
from .paged_decode import paged_decode, paged_prefill
from .mamba2_ssd import ssd_chunked
from .moe_gmm import gmm as gmm_pallas
from .uts_expand import uts_expand


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, scale=None, impl: str = "auto",
              block_q: int = 128, block_k: int = 128, lengths=None,
              block_tables=None, q_offset=None):
    """impl: auto | pallas | pallas_interpret | ref | chunked
          | decode | decode_interpret | decode_ref
          | paged | paged_interpret | paged_ref

    `lengths` ((B,) i32 visible-window sizes against a padded KV cache)
    plus Sq == 1 selects the split-KV flash-decode fast path: `auto`
    routes such calls to the decode kernel on TPU and the masked-window
    oracle elsewhere; the decode_* impls force one arm.

    `block_tables` ((B, max_blocks) i32) additionally marks k/v as flat
    (num_blocks, Hkv, block_size, D) KV *pools* indirected per sequence
    through the table (serve/kvpool.py): calls route to the paged
    flash-decode kernel on TPU and the gather oracle elsewhere. Every
    impl spelling is normalized so one config knob drives contiguous and
    paged decode alike — the window mask and table walk are never
    dropped.

    `q_offset` ((B,) i32, paged only) marks the call as a *chunked
    prefill*: q holds Sq tokens at absolute positions
    ``[q_offset, q_offset + Sq)`` attending causally to the pool window
    ``[0, lengths)`` — the kernel/oracle pair that lets a long admission
    prefill in budget-sized chunks against blocks earlier chunks (or a
    prefix-cache hit) already wrote.
    """
    if q_offset is not None and block_tables is None:
        raise ValueError(
            "q_offset is a paged chunked-prefill parameter and requires "
            "block_tables; the contiguous paths would silently ignore "
            "the offset and compute wrong attention"
        )
    if lengths is not None and q.shape[1] != 1 and block_tables is None:
        raise ValueError(
            f"lengths is only supported for Sq == 1 decode, got Sq="
            f"{q.shape[1]}; dropping the window mask would silently "
            "attend to dead cache rows"
        )
    if block_tables is not None:
        if lengths is None:
            raise ValueError("block_tables requires lengths")
        impl = {
            "auto": "paged" if _on_tpu() else "paged_ref",
            "pallas": "paged",
            "pallas_interpret": "paged_interpret",
            "ref": "paged_ref",
            "chunked": "paged_ref",
            "decode": "paged",
            "decode_interpret": "paged_interpret",
            "decode_ref": "paged_ref",
        }.get(impl, impl)
        if q_offset is not None or q.shape[1] != 1:
            if q_offset is None:
                raise ValueError(
                    "paged attention with Sq > 1 is chunked prefill and "
                    "requires q_offset (the chunk's start position)"
                )
            if impl == "paged_ref":
                return ref.paged_prefill_ref(q, k, v, block_tables,
                                             lengths, q_offset, scale=scale)
            assert impl in ("paged", "paged_interpret"), impl
            return paged_prefill(q, k, v, block_tables, lengths, q_offset,
                                 scale=scale,
                                 interpret=(impl == "paged_interpret"))
        if impl == "paged_ref":
            return ref.paged_decode_ref(q, k, v, block_tables, lengths,
                                        scale=scale)
        assert impl in ("paged", "paged_interpret"), impl
        return paged_decode(q, k, v, block_tables, lengths, scale=scale,
                            interpret=(impl == "paged_interpret"))
    is_decode = lengths is not None
    if is_decode:
        # Normalize the prefill impl names so one config knob drives both
        # paths: the window mask must never be dropped once lengths are in.
        impl = {
            "auto": "decode" if _on_tpu() else "decode_ref",
            "pallas": "decode",
            "pallas_interpret": "decode_interpret",
            "ref": "decode_ref",
            "chunked": "decode_ref",
        }.get(impl, impl)
    elif impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl in ("decode", "decode_interpret", "decode_ref"):
        assert is_decode, "decode impls need Sq == 1 and lengths"
        if impl == "decode_ref":
            return ref.decode_ref(q, k, v, lengths, scale=scale)
        return flash_decode(q, k, v, lengths, scale=scale,
                            interpret=(impl == "decode_interpret"))
    if impl == "ref":
        return ref.attention_ref(q, k, v, causal=causal, scale=scale)
    if impl == "chunked":
        return ref.attention_chunked(q, k, v, causal=causal, scale=scale,
                                     block_q=block_q if block_q > 128 else 512)
    return flash_attention(
        q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=(impl == "pallas_interpret"),
    )


def ssd(x, dt, A, B, C, *, chunk: int = 64, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return ref.ssd_ref(x, dt, A, B, C)
    if impl == "chunked":
        return ref.ssd_chunked_ref(x, dt, A, B, C, chunk=max(chunk, 128))
    return ssd_chunked(x, dt, A, B, C, chunk=chunk,
                       interpret=(impl == "pallas_interpret"))


def gmm(x, w, group_sizes, *, impl: str = "auto", block_t=128, block_f=128):
    """Grouped matmul for sort-dispatched MoE expert compute."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return ref.gmm_ref(x, w, group_sizes)
    return gmm_pallas(x, w, group_sizes, block_t=block_t, block_f=block_f,
                      interpret=(impl == "pallas_interpret"))


def expand_uts(d0, d1, base, thresholds, *, width=64, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return ref.uts_expand_ref(d0, d1, base, thresholds, width)
    return uts_expand(d0, d1, base, thresholds, width=width,
                      interpret=(impl == "pallas_interpret"))
