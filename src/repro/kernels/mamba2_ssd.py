"""Pallas TPU kernel for the Mamba2 SSD (state-space duality) chunk scan.

The SSD insight: a selective-state-space recurrence
    h_t = exp(A·dt_t) h_{t-1} + dt_t·x_t ⊗ B_t,    y_t = C_t h_t
can be evaluated chunk-wise with matmuls (MXU work) plus a tiny inter-chunk
state carry. For a chunk of length L with inclusive log-decay prefix
s_t = A·Σ_{τ<=t} dt_τ:

    y_intra = ((C Bᵀ) ∘ M) (dt·x)        M[t,τ] = exp(s_t - s_τ)·[τ<=t]
    y_inter = exp(s_t) · (C h_in)
    h_out   = exp(s_L) h_in + Bᵀ diag(exp(s_L - s_t)·dt) x

Grid = (batch, heads, chunks) with chunks innermost (sequential on TPU); the
(N, P) state lives in VMEM scratch across chunk steps. exp arguments are all
<= 0 (A < 0), so the chunk math is numerically tame.

Layout: the wrapper splits time into (chunks, L) and moves heads ahead of
it, so every block's last two dims are a whole (L, P), (L, N), (L, 1) or
(1, L) array slice — shapes the TPU compiler accepts for any L, P, N. The
per-chunk prefix s is computed in XLA before the call (a cumsum over L) and
handed in both as a column (L, 1) and a row (1, L), which gives the (L, L)
decay matrix by broadcasting without an in-kernel transpose.

Oracle: ref.ssd_ref (sequential lax.scan). Single B/C group (n_groups=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, sc_ref, sr_ref, b_ref, c_ref, y_ref, hout_ref,
            h_ref, *, chunk, nchunks):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)         # (L, P)
    dt = dt_ref[0, 0, 0]                           # (L, 1)
    s = sc_ref[0, 0, 0]                            # (L, 1) inclusive, <= 0
    s_row = sr_ref[0, 0, 0]                        # (1, L) the same prefix
    Bm = b_ref[0, 0].astype(jnp.float32)           # (L, N)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (L, N)
    dx = dt * x                                    # (L, P)

    # intra-chunk: ((C B^T) o M) dx
    g = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, L)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tau_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(t_idx >= tau_idx,
                  jnp.exp(jnp.minimum(s - s_row, 0.0)), 0.0)
    y = jax.lax.dot_general(g * m, dx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (L, P)

    # inter-chunk: exp(s_t) C_t h_in
    h_in = h_ref[...]                              # (N, P)
    y += jnp.exp(s) * jax.lax.dot_general(
        Cm, h_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: h_out = exp(s_L) h_in + B^T diag(exp(s_L - s)) dx
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    s_l = jnp.sum(jnp.where(lane == chunk - 1, s_row, 0.0), axis=1,
                  keepdims=True)                   # (1, 1)
    wts = jnp.exp(s_l - s) * dx                    # (L, P)
    h_new = jnp.exp(s_l) * h_in + jax.lax.dot_general(
        Bm, wts, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_ref[...] = h_new
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nchunks - 1)
    def _final():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked(x, dt, A, B, C, *, chunk: int = 64, interpret: bool = False):
    """x (Bt,T,H,P), dt (Bt,T,H), A (H,), B/C (Bt,T,N) -> y, h_final."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]
    L = min(chunk, T)
    assert T % L == 0, (T, L)
    nchunks = T // L
    grid = (Bt, H, nchunks)

    # (Bt, T, H, ...) -> (Bt, H, chunks, L, ...): heads first, time chunked.
    xt = x.reshape(Bt, nchunks, L, H, P).transpose(0, 3, 1, 2, 4)
    dtt = dt.astype(jnp.float32).reshape(Bt, nchunks, L, H).transpose(
        0, 3, 1, 2)
    s = A.astype(jnp.float32)[None, :, None, None] * jnp.cumsum(dtt, axis=-1)
    Bc = B.reshape(Bt, nchunks, L, N)
    Cc = C.reshape(Bt, nchunks, L, N)

    col = pl.BlockSpec((1, 1, 1, L, 1), lambda b, h, c: (b, h, c, 0, 0))
    bc_spec = pl.BlockSpec((1, 1, L, N), lambda b, h, c: (b, c, 0, 0))
    kernel = functools.partial(_kernel, chunk=L, nchunks=nchunks)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, h, c: (b, h, c, 0, 0)),
            bc_spec,
            bc_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
            jax.ShapeDtypeStruct((Bt, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xt, dtt[..., None], s[..., None], s[..., None, :], Bc, Cc)
    return y.transpose(0, 2, 3, 1, 4).reshape(Bt, T, H, P), h
