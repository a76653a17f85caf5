"""Pallas TPU kernel for the UTS hot loop: batched node expansion.

The paper's ``process(n)`` spends all its time hashing child descriptors and
sampling geometric child counts (§2.5.2). That is pure VPU work: 32-bit
integer mixing over a (nodes × width) block. The kernel expands a block of M
nodes × W child indices per grid step, entirely in VMEM.

Geometric sampling is a table of 32 integer threshold compares (bit-exact
with the python oracle; see problems/uts.py).

Oracle: ref.uts_expand_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.problems.uts import MAX_CHILD, _C1, _C2, _C3, _C4


def _fmix32(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(_C1)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(_C2)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _kernel(thr_ref, d0_ref, d1_ref, base_ref, cd0_ref, cd1_ref, m_ref, *,
            width):
    mb = d0_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (mb, width), 1)
    idx = (base_ref[...] + lane).astype(jnp.uint32)    # (mb, 1) + lanes
    h0 = _fmix32(d0_ref[...] + idx * jnp.uint32(_C3))
    h1 = _fmix32((d1_ref[...] ^ h0) + idx * jnp.uint32(_C4))
    h0 = _fmix32(h0 ^ h1)
    cd0_ref[...] = h0
    cd1_ref[...] = h1
    # geometric child count: #{k : u < T_k} over the threshold table,
    # read as scalars from SMEM (scalar prefetch)
    m = jnp.zeros((mb, width), jnp.int32)
    for kk in range(MAX_CHILD):  # static unroll; VPU compares
        thr = thr_ref[kk].astype(jnp.uint32)
        m = m + (h0 < thr).astype(jnp.int32)
    m_ref[...] = m


@functools.partial(jax.jit, static_argnames=("width", "block_m", "interpret"))
def uts_expand(d0, d1, base, thresholds, *, width: int = 64,
               block_m: int = 128, interpret: bool = False):
    """d0,d1 (M,) uint32; base (M,) i32; thresholds (MAX_CHILD,) uint32.
    Returns cd0, cd1 (M, width) uint32 and m (M, width) i32.

    Per-node inputs travel as (M, 1) columns so each block is (block_m, 1)
    — node rows on sublanes, broadcast along the child lanes — which the
    TPU compiler tiles (a 1-D (block_m,) block does not match XLA's
    layout for 1-D u32 arrays)."""
    from jax.experimental.pallas import tpu as pltpu

    M = d0.shape[0]
    mb = min(block_m, M)
    assert M % mb == 0, (M, mb)
    kernel = functools.partial(_kernel, width=width)
    col = pl.BlockSpec((mb, 1), lambda i, thr: (i, 0))
    out = pl.BlockSpec((mb, width), lambda i, thr: (i, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M // mb,),
            in_specs=[col, col, col],
            out_specs=[out, out, out],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((M, width), jnp.uint32),
            jax.ShapeDtypeStruct((M, width), jnp.uint32),
            jax.ShapeDtypeStruct((M, width), jnp.int32),
        ],
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(thresholds, jnp.int32),
      d0[:, None], d1[:, None], base[:, None])
