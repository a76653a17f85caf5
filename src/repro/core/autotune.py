"""GLB parameter auto-tuning — the paper's future-work item (4): "Provide a
mechanism to auto-tune GLB parameters (e.g., task granularity, size of
random victims/lifeline buddies)".

Strategy: short probe runs in sim mode over a small (w, z, n) grid on a
scaled-down instance of the user's problem, scored by makespan (supersteps)
with idle fraction as the tie-breaker — the quantities the paper's log
exposes for manual tuning (§2.4). Deterministic given the seed.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import NamedTuple, Sequence

import numpy as np

from .params import GLBParams
from .problem import GLBProblem
from .scheduler import run_sim


class TuneResult(NamedTuple):
    best: GLBParams
    table: list  # (params, supersteps, idle_frac)


# ------------------------------------------------------- kernel block table
# Split-KV flash-decode block sizes, keyed by head_dim: how many KV cache
# rows one grid step streams through VMEM. The working set per step is
# ~2 * block_k * head_dim * 4B (k + v tiles, double-buffered by the
# pipeline), so wider heads take smaller blocks to stay well inside the
# ~16 MB VMEM budget; all entries are 128-multiples for MXU lane alignment.
DECODE_BLOCK_K = {32: 512, 64: 512, 128: 256, 256: 128}


def decode_block_k(kv_len: int, head_dim: int) -> int:
    """KV block size for kernels.flash_decode: table lookup by head_dim
    with a halving fallback so the block always divides the (bucketed)
    cache length."""
    return _block_from_table(DECODE_BLOCK_K, kv_len, head_dim)


# Paged KV pool block sizes (tokens per block), keyed by head_dim. The
# paged kernel streams exactly one pool block per grid step, so this is
# both the allocator granularity and the kernel tile: small enough that
# internal fragmentation (the partially-filled tail block per sequence)
# stays low at production request lengths, large enough that the (group, D) x
# (Bs, D)^T step keeps the MXU lanes busy and the per-block DMA amortizes.
# 4-8x smaller than DECODE_BLOCK_K — the contiguous kernel pays
# fragmentation at *bucket* granularity instead, so it wants big tiles.
PAGED_BLOCK_KV = {32: 64, 64: 64, 128: 32, 256: 16}


def paged_block_kv(max_seq: int, head_dim: int) -> int:
    """Pool/kernel block size for kernels.paged_decode: table lookup by
    head_dim, halved until it divides the per-sequence cache cap (the
    block-table width max_seq // block must be exact)."""
    return _block_from_table(PAGED_BLOCK_KV, max_seq, head_dim)


def _block_from_table(table: dict, length: int, head_dim: int) -> int:
    bk = min(table.values())
    for hd in sorted(table):
        if head_dim <= hd:
            bk = table[hd]
            break
    bk = max(1, min(bk, length))
    while length % bk:
        bk //= 2
    return max(bk, 1)


def autotune(
    problem: GLBProblem,
    P: int,
    base: GLBParams = GLBParams(),
    w_grid: Sequence[int] = (0, 1, 2, 4),
    z_grid: Sequence[int] = (0, 2),          # 0 => log2(P) cap
    n_grid: Sequence[int] = (32, 128, 512),
    seed: int = 0,
    max_supersteps: int = 50_000,
) -> TuneResult:
    rows = []
    for w, z, n in itertools.product(w_grid, z_grid, n_grid):
        params = dataclasses.replace(
            base, w=w, z=z, n=n, max_supersteps=max_supersteps
        )
        out = run_sim(problem, P, params, seed=seed)
        if not bool(np.asarray(out.converged)):
            continue
        steps = int(out.supersteps)
        idle = float(
            np.asarray(out.stats["idle_steps"]).sum() / max(steps * P, 1)
        )
        # score: wall-clock proxy = supersteps x per-superstep cost (~n)
        rows.append((params, steps, idle))
    if not rows:
        raise RuntimeError("no converging configuration found")
    rows.sort(key=lambda r: (r[1] * max(r[0].n, 1), r[2]))
    return TuneResult(best=rows[0][0], table=rows)
