"""Betweenness Centrality (paper §2.6) as a GLB problem.

Exact Brandes (K4approx = SCALE) on an SSCA2 R-MAT graph that is replicated
on every place — the paper's "very strong assumption" that the graph fits in
one place's memory, which makes tasks relocatable. A task item is a vertex
interval (low, high) (§2.6.2); split halves every interval; merge
concatenates; the result is the betweenness map, reduced element-wise.

The paper found that even a task granularity of ONE vertex was too coarse —
workers could not respond to steal requests mid-vertex — and rewrote the
per-vertex computation as an *interruptable state machine*. We implement
exactly that: the Brandes forward/backward sweeps live in `state` and
`process(budget)` advances a bounded number of frontier sweeps (each one
matvec against the replicated adjacency), yielding between sweeps. The
in-progress vertex is reported via ``work_in_state`` so GLB's hunger and
termination logic accounts for it.

Frontier sweeps are dense matvecs so the hot loop maps onto the MXU. They
run at HIGHEST precision: path counts sigma outgrow bfloat16's 8-bit
mantissa, and a TPU computes an f32 matmul in one bfloat16 pass by default.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.problem import GLBProblem
from repro.core import taskbag as tb

HIGHEST = jax.lax.Precision.HIGHEST

ITEM_SPEC = {
    "lo": jax.ShapeDtypeStruct((), jnp.int32),
    "hi": jax.ShapeDtypeStruct((), jnp.int32),
}


def bc_problem(adj: np.ndarray, capacity: int = 512, static_init: bool = True):
    """adj: dense (N, N) float32 adjacency, row=src col=dst, replicated."""
    n = adj.shape[0]
    adj_const = np.asarray(adj, np.float32)

    def init_place(p, P):
        bag = tb.make_bag(ITEM_SPEC, capacity)
        if static_init:
            # Paper BC: vertices statically partitioned, GLB rebalances.
            lo = (p * n) // P
            hi = ((p + 1) * n) // P
            bag = tb.push_one(bag, {"lo": lo.astype(jnp.int32),
                                    "hi": hi.astype(jnp.int32)})
            bag["size"] = jnp.where(hi > lo, bag["size"], 0)
        else:
            bag = tb.push_one(
                bag, {"lo": jnp.int32(0), "hi": jnp.int32(n)}
            )
            bag["size"] = jnp.where(p == 0, bag["size"], 0)
        state = {
            "bc": jnp.zeros((n,), jnp.float32),
            "cur": jnp.int32(-1),    # in-progress source vertex
            "phase": jnp.int32(0),   # 0 = forward BFS, 1 = backward deps
            "level": jnp.int32(0),
            "dist": jnp.full((n,), -1, jnp.int32),
            "sigma": jnp.zeros((n,), jnp.float32),
            "delta": jnp.zeros((n,), jnp.float32),
        }
        return state, bag

    def process(state, bag, budget: int):
        A = jnp.asarray(adj_const)  # replicated reference state (§2.1)

        def start_vertex(st, b):
            b, item = tb.pop_tail(b)
            v = item["lo"]
            rest = {"lo": (item["lo"] + 1)[None], "hi": item["hi"][None]}
            b = tb.push_block(b, rest, (item["hi"] - item["lo"] > 1).astype(jnp.int32))
            st = dict(
                st,
                cur=v,
                phase=jnp.int32(0),
                level=jnp.int32(0),
                dist=jnp.full((n,), -1, jnp.int32).at[v].set(0),
                sigma=jnp.zeros((n,), jnp.float32).at[v].set(1.0),
                delta=jnp.zeros((n,), jnp.float32),
            )
            return st, b

        def forward_sweep(st):
            frontier = (st["dist"] == st["level"]).astype(jnp.float32)
            # contributions to dst
            reach = jnp.dot(st["sigma"] * frontier, A, precision=HIGHEST)
            new = (st["dist"] < 0) & (reach > 0)
            dist = jnp.where(new, st["level"] + 1, st["dist"])
            sigma = st["sigma"] + reach * new
            any_new = new.any()
            return dict(
                st,
                dist=dist,
                sigma=sigma,
                level=jnp.where(any_new, st["level"] + 1, st["level"]),
                phase=jnp.where(any_new, 0, 1).astype(jnp.int32),
            )

        def backward_sweep(st):
            # Predecessor accumulation from depth `level` to `level - 1`.
            at_l = (st["dist"] == st["level"]).astype(jnp.float32)
            coef = jnp.where(
                at_l > 0, (1.0 + st["delta"]) / jnp.maximum(st["sigma"], 1e-30), 0.0
            )
            contrib = jnp.dot(A, coef, precision=HIGHEST)  # sum over succ.
            at_prev = (st["dist"] == st["level"] - 1).astype(jnp.float32)
            delta = st["delta"] + st["sigma"] * contrib * at_prev
            lvl = st["level"] - 1
            finished = lvl <= 0
            bc = jnp.where(
                finished,
                st["bc"] + delta.at[st["cur"]].set(0.0),  # exclude the source
                st["bc"],
            )
            return dict(
                st,
                delta=jnp.where(finished, jnp.zeros_like(delta), delta),
                level=jnp.where(finished, 0, lvl),
                bc=bc,
                cur=jnp.where(finished, -1, st["cur"]),
                phase=jnp.where(finished, 0, st["phase"]).astype(jnp.int32),
            )

        def cond(c):
            st, b, left = c
            has_work = (st["cur"] >= 0) | (b["size"] > 0)
            return (left > 0) & has_work

        def body(c):
            st, b, left = c
            need_start = st["cur"] < 0

            def do_start(args):
                st, b = args
                return start_vertex(st, b)

            st, b = jax.lax.cond(need_start, do_start, lambda a: a, (st, b))
            st = jax.lax.cond(
                st["phase"] == 0,
                forward_sweep,
                backward_sweep,
                st,
            )
            return st, b, left - 1

        state, bag, left = jax.lax.while_loop(
            cond, body, (state, bag, jnp.int32(budget))
        )
        return state, bag, jnp.int32(budget) - left

    def split(bag, k: int):
        blk = tb.read_front(bag, k)
        lane = jnp.arange(k, dtype=jnp.int32)
        in_bag = lane < jnp.minimum(bag["size"], k)
        c = blk["hi"] - blk["lo"]
        splittable = in_bag & (c >= 2)
        mid = blk["lo"] + (c + 1) // 2
        keep = dict(blk, hi=jnp.where(splittable, mid, blk["hi"]))
        bag2 = tb.write_front(bag, keep)
        give = {"lo": mid, "hi": blk["hi"]}
        items, count = tb.compact_block(give, splittable)
        return bag2, {"items": items, "count": count}

    def evacuate(state, bag):
        # Crash recovery (DESIGN.md §15): re-bag the in-progress source
        # vertex as a width-1 interval and reset the sweep. Exact,
        # because ``bc`` only accumulates when a backward sweep
        # FINISHES — a restarted vertex recomputes from scratch on the
        # survivor and contributes exactly once.
        v = jnp.maximum(state["cur"], 0)
        bag = tb.push_block(
            bag, {"lo": v[None], "hi": (v + 1)[None]},
            (state["cur"] >= 0).astype(jnp.int32),
        )
        state = dict(state, cur=jnp.int32(-1), phase=jnp.int32(0),
                     level=jnp.int32(0))
        return state, bag

    return GLBProblem(
        name=f"bc-n{n}",
        item_spec=ITEM_SPEC,
        capacity=capacity,
        init_place=init_place,
        process=process,
        split=split,
        merge=tb.merge_packet,
        result=lambda st: st["bc"],
        reduce_op="sum",
        work_in_state=lambda st: (st["cur"] >= 0).astype(jnp.int32),
        evacuate=evacuate,
    )
