"""Mixture-of-Experts layer: top-k router, capacity-bounded dispatch, aux
load-balancing loss — plus the paper's technique applied to experts:
a GLB-style expert-placement rebalancer (see glb_moe.py) that migrates /
swaps experts between EP ranks based on observed load, exactly the paper's
"observe imbalance -> steal work" loop at the granularity of expert shards.

Dispatch is einsum-based (one-hot combine/dispatch tensors), the standard
TPU-friendly formulation; the expert axis is sharded over the `model` mesh
axis (EP).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init


def moe_init(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (D, E)),
        "wg": dense_init(ks[1], (E, D, F), in_axis=1),
        "wi": dense_init(ks[2], (E, D, F), in_axis=1),
        "wo": dense_init(ks[3], (E, F, D), in_axis=1),
    }


def moe_fwd(p, x, cfg: ModelConfig, expert_perm=None) -> Tuple[jax.Array, dict]:
    """x: (B, S, D) -> (y, aux). aux carries the load-balancing loss term and
    per-expert token counts (the GLB rebalancer's input signal).

    expert_perm: optional (E,) i32 permutation from the GLB expert-placement
    rebalancer; logically expert e's weights live at slot expert_perm[e].

    Dispatch impls (cfg.moe_impl):
      global — single global-view scatter/gather (reference semantics; GSPMD
               replicates the expert buffers at scale — see EXPERIMENTS §Perf)
      ep     — shard_map expert parallelism: activations are replicated over
               `model`, so each model-rank dispatches ONLY to its E/ranks
               local experts and the combine is one psum; collective traffic
               is one (B_loc,S,D) all-reduce per layer instead of replicated
               (E,cap,D) buffers.
      auto   — ep when an ambient mesh with a `model` axis exists.

    ep differences vs global (both tested): capacity truncation happens per
    DP shard; the aux loss is the per-shard Switch estimator (pmean of
    fe_local·me_local), standard in EP frameworks."""
    mesh = None
    if cfg.moe_impl in ("auto", "ep"):
        from repro.dist.sharding import ambient_mesh

        m = ambient_mesh()
        if (m is not None and "model" in m.shape
                and cfg.n_experts % m.shape["model"] == 0):
            mesh = m
        if cfg.moe_impl == "ep" and mesh is None:
            raise ValueError("moe_impl='ep' needs an ambient mesh with a "
                             "'model' axis dividing n_experts")
    if mesh is not None:
        return _moe_fwd_ep(p, x, cfg, expert_perm, mesh)
    return _moe_fwd_global(p, x, cfg, expert_perm)


def _moe_fwd_global(p, x, cfg: ModelConfig, expert_perm=None):
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    dt = x.dtype
    xt = x.reshape(T, D)

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)                # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )
    if expert_perm is not None:
        gate_idx = expert_perm[gate_idx]

    # aux load-balance loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                                  # (E,)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)       # (T, K, E)
    fe = onehot.sum(axis=(0, 1)) / (T * K)
    aux_loss = E * jnp.sum(fe * me)

    # capacity-bounded dispatch, scatter/gather form: no (T,E,cap)
    # intermediates, so it scales to millions of global tokens under pjit
    cap = int(max(1, round(T * K / E * cfg.capacity_factor)))
    flat = onehot.reshape(T * K, E)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)      # queue pos
    keep = (pos < cap) * onehot                                    # (T, K, E)
    pos_in = (pos * keep).sum(-1).astype(jnp.int32)                # (T, K)
    kept = keep.sum(-1)                                            # (T, K)

    from repro.dist.sharding import shard_act

    # scatter tokens into expert slot buffers; dropped rows hit the
    # sentinel expert row E (sliced off afterwards)
    xe = jnp.zeros((E + 1, cap, D), dt)
    for kk in range(K):  # K is small and static
        e_k = jnp.where(kept[:, kk] > 0, gate_idx[:, kk], E).astype(jnp.int32)
        xe = xe.at[e_k, pos_in[:, kk]].add(xt)
    xe = shard_act(xe[:E], "expert", "batch", "none")              # EP slots

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))          # (E,cap,D)
    ye = jnp.concatenate([ye, jnp.zeros((1, cap, D), dt)], axis=0)

    # combine: gather each (t,k) slot back, weighted by its gate
    y = jnp.zeros((T, D), dt)
    for kk in range(K):
        e_k = jnp.where(kept[:, kk] > 0, gate_idx[:, kk], E).astype(jnp.int32)
        y = y + ye[e_k, pos_in[:, kk]] * gate_vals[:, kk, None].astype(dt)
    y = y.reshape(B, S, D)

    counts = onehot.sum(axis=(0, 1))                                # (E,)
    dropped = (1.0 - kept).sum()
    return y, {"aux_loss": aux_loss, "expert_counts": counts,
               "dropped": dropped, "capacity": cap}


def _rank_within_expert(gate_idx_flat, E: int):
    """Queue position of each routed (t,k) slot within its expert, via a
    stable sort — O(T·K) vectors instead of the (T·K, E) dense cumsum
    (EXPERIMENTS §Perf iteration 2: the routing-buffer bytes dominated)."""
    n = gate_idx_flat.shape[0]
    order = jnp.argsort(gate_idx_flat, stable=True)
    sorted_e = gate_idx_flat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    counts = jnp.bincount(gate_idx_flat, length=E)
    return pos, counts


def _moe_fwd_ep(p, x, cfg: ModelConfig, expert_perm, mesh):
    """shard_map EP dispatch; see moe_fwd docstring. Math matches the
    global impl up to per-DP-shard (vs global) capacity truncation and
    dispatch-queue order (sort-based ranking)."""
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    dt = x.dtype
    dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_ranks = mesh.shape["model"]
    E_loc = E // n_ranks
    perm = (jnp.arange(E, dtype=jnp.int32) if expert_perm is None
            else jnp.asarray(expert_perm, jnp.int32))

    def inner(router, wg, wi, wo, perm_, xl):
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xt = xl.reshape(Tl, D)
        logits = (xt @ router.astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        gate_idx = perm_[gate_idx]

        # routing stats from (T·K,) vectors — no (T,K,E) one-hots
        flat_e = gate_idx.reshape(Tl * K)
        pos_flat, counts_local = _rank_within_expert(flat_e, E)
        me = jnp.mean(probs, axis=0)
        fe = counts_local.astype(jnp.float32) / (Tl * K)
        aux_local = E * jnp.sum(fe * me)
        counts_local = counts_local.astype(jnp.float32)

        cap = int(max(1, round(Tl * K / E * cfg.capacity_factor)))
        pos_in = pos_flat.reshape(Tl, K)
        kept = (pos_in < cap).astype(jnp.float32)

        # local dispatch: only my E_loc experts; everything else -> sentinel
        lo = jax.lax.axis_index("model").astype(jnp.int32) * E_loc
        xe = jnp.zeros((E_loc + 1, cap, D), dt)
        rels = []
        for kk in range(K):
            rel = gate_idx[:, kk] - lo
            ok = (kept[:, kk] > 0) & (rel >= 0) & (rel < E_loc)
            rel = jnp.where(ok, rel, E_loc)
            rels.append(rel)
            xe = xe.at[rel, pos_in[:, kk]].add(xt)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe[:E_loc],
                                   wg.astype(dt)))
        h = h * jnp.einsum("ecd,edf->ecf", xe[:E_loc], wi.astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))
        ye = jnp.concatenate([ye, jnp.zeros((1, cap, D), dt)], axis=0)

        y = jnp.zeros((Tl, D), dt)
        for kk in range(K):
            y = y + ye[rels[kk], pos_in[:, kk]] * gate_vals[:, kk, None].astype(dt)
        # each token's experts live on exactly one rank each -> psum = combine
        y = jax.lax.psum(y, "model").reshape(Bl, Sl, D)

        if dp:
            aux_local = jax.lax.pmean(aux_local, dp)
            counts_local = jax.lax.psum(counts_local, dp)
            drop = jax.lax.psum((1.0 - kept).sum(), dp)
        else:
            drop = (1.0 - kept).sum()
        return y, aux_local, counts_local, drop

    y, aux_loss, counts, dropped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(None, None),                 # router, gathered/replicated
            P("model", None, None),        # wg  (EP on the expert axis)
            P("model", None, None),        # wi
            P("model", None, None),        # wo
            P(None),                       # perm
            P(dp if dp else None, None, None),  # x: DP over batch
        ),
        out_specs=(P(dp if dp else None, None, None), P(), P(), P()),
        check_vma=False,
    )(p["router"], p["wg"], p["wi"], p["wo"], perm, x)
    cap = int(max(1, round(B * S * K / E * cfg.capacity_factor)))
    return y, {"aux_loss": aux_loss, "expert_counts": counts,
               "dropped": dropped, "capacity": cap}
