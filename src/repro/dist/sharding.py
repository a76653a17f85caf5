"""Logical-axis sharding rule engine (DESIGN.md §5).

Every tensor in the system — param leaves, optimizer state, inputs, KV /
SSM caches, activations — is described by a tuple of *logical* axis names
("embed", "qkv", "batch", "cache_seq", ...). This module owns the single
table that maps logical names to mesh axes and resolves any (shape,
logical-axes, mesh) triple into a concrete ``PartitionSpec``:

  * divisibility fallback — a mesh axis that does not divide the dimension
    is dropped (replicate rather than produce an uneven GSPMD split);
  * multi-axis batch — "batch" maps to ``("pod", "data")`` so the same rule
    covers single-pod (data only) and multi-pod (DP over DCN) meshes, taking
    every dividing axis in rule order (a non-dividing axis is skipped, later
    candidates are still tried);
  * per-tensor conflict resolution — a mesh axis is consumed at most once
    per spec, first (leftmost) logical axis wins, later claimants replicate.

The layout this encodes is FSDP("data") x TP/EP("model") x DP("pod","data"):
weight embed dims shard over `data` (ZeRO-3 style), head/ffn/expert/vocab
dims over `model`, batch dims over (`pod`, `data`), and decode KV caches
spread their sequence dim over `model`.

Only ``mesh.shape`` (a name->size mapping) is consulted, so tests can pass
lightweight fakes; ``tree_shardings`` needs a real device mesh.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.tree_util as jtu
from jax.sharding import NamedSharding, PartitionSpec as P

Axes = Tuple[str, ...]

# Logical axis -> ordered mesh-axis candidates. An empty tuple means
# "always replicated".
LOGICAL_AXIS_RULES: Dict[str, Tuple[str, ...]] = {
    # data-ish dims
    "batch": ("pod", "data"),
    "seq": (),
    "act_embed": (),
    # weight dims
    "embed": ("data",),          # FSDP / ZeRO-3: weight embed dim over data
    "vocab": ("model",),
    "qkv": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "expert": ("model",),        # expert parallelism
    "d_inner": ("model",),       # mamba inner channels (TP)
    # cache dims
    "cache_seq": ("model",),     # decode KV cache: sequence over model
    # structural / replicated
    "layer": (),
    "conv": (),
    "state": (),
    "none": (),
}


def spec_for(shape: Tuple[int, ...], axes: Axes, mesh) -> P:
    """Resolve logical ``axes`` for a tensor of ``shape`` on ``mesh``.

    ``mesh`` needs only a ``.shape`` mapping of axis name -> size.
    """
    if len(shape) != len(axes):
        raise ValueError(
            f"rank mismatch: shape {shape} vs logical axes {axes}"
        )
    mesh_shape = dict(mesh.shape)
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        try:
            rule = LOGICAL_AXIS_RULES[name]
        except KeyError:
            raise KeyError(
                f"unknown logical axis {name!r}; known: "
                f"{sorted(LOGICAL_AXIS_RULES)}"
            ) from None
        picked = []
        rem = int(dim)
        for ax in rule:
            n = mesh_shape.get(ax)
            if n is None or ax in used:
                continue
            if rem % n == 0:
                picked.append(ax)
                used.add(ax)
                rem //= n
        if not picked:
            entries.append(None)
        elif len(picked) == 1:
            entries.append(picked[0])
        else:
            entries.append(tuple(picked))
    return P(*entries)


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, str) for e in x)


def tree_specs(axes_tree, shapes_tree, mesh):
    """Map a logical-axes tree + matching shape tree -> PartitionSpec tree."""
    return jax.tree.map(
        lambda ax, s: spec_for(tuple(s.shape), ax, mesh),
        axes_tree, shapes_tree, is_leaf=_is_axes_leaf,
    )


def tree_shardings(axes_tree, shapes_tree, mesh):
    """Like ``tree_specs`` but wraps each spec in a NamedSharding (real
    device mesh required) — the form jit in_shardings/out_shardings take."""
    specs = tree_specs(axes_tree, shapes_tree, mesh)
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# --------------------------------------------------------------- params
_ATTN_AXES = {
    "wq": ("embed", "qkv"), "wk": ("embed", "qkv"), "wv": ("embed", "qkv"),
    "wo": ("qkv", "embed"),
    "bq": ("qkv",), "bk": ("qkv",), "bv": ("qkv",),
}
_MLP_AXES = {
    "wg": ("embed", "ffn"), "wi": ("embed", "ffn"), "wo": ("ffn", "embed"),
}
_MOE_AXES = {
    "router": ("embed", "none"),
    # pure EP: `model` is consumed by the expert dim, so the ffn dim
    # conflict-resolves to replicated within each expert shard
    "wg": ("expert", "embed", "ffn"),
    "wi": ("expert", "embed", "ffn"),
    "wo": ("expert", "ffn", "embed"),
}
_MAMBA_AXES = {
    "wz": ("embed", "d_inner"), "wx": ("embed", "d_inner"),
    "wB": ("embed", "none"), "wC": ("embed", "none"),
    "wdt": ("embed", "none"),
    "conv_x": ("conv", "d_inner"),
    "conv_B": ("conv", "none"), "conv_C": ("conv", "none"),
    "A_log": ("none",), "D": ("none",), "dt_bias": ("none",),
    "gate_norm": ("d_inner",),
    "out_proj": ("d_inner", "embed"),
}
_BY_PARENT = {
    "attn": _ATTN_AXES, "mlp": _MLP_AXES, "moe": _MOE_AXES,
    "mamba": _MAMBA_AXES,
}
_NORMS = {"ln", "ln1", "ln2", "final_ln"}


def _key_name(entry) -> str:
    return str(getattr(entry, "key", getattr(entry, "name", entry)))


def _param_leaf_axes(path, ndim: int) -> Axes:
    keys = [_key_name(k) for k in path]
    name = keys[-1]
    parent = keys[-2] if len(keys) >= 2 else None
    stacked = keys[0] == "blocks"  # vmapped layer stack: leading layer dim

    if name == "embed":
        base = ("vocab", "embed") if ndim == 2 else ("none", "vocab", "embed")
    elif name == "head":
        base = ("embed", "vocab") if ndim == 2 else ("none", "embed", "vocab")
    elif name in _NORMS:
        base = ("embed",)
    elif parent in _BY_PARENT and name in _BY_PARENT[parent]:
        base = _BY_PARENT[parent][name]
    else:
        raise KeyError(
            f"no logical-axis rule for param leaf {'/'.join(keys)!r}"
        )
    axes = (("layer",) + base) if stacked else base
    if len(axes) != ndim:
        raise ValueError(
            f"param leaf {'/'.join(keys)!r}: rank {ndim} != axes {axes}"
        )
    return axes


def param_axes(cfg, pshapes=None) -> Any:
    """Logical-axes pytree matching ``init_lm(key, cfg)`` for any registered
    arch (dense / moe / ssm / hybrid / vlm / audio). Pass ``pshapes`` (an
    ``eval_shape`` of the init) when the caller already has it, to avoid
    re-tracing the full model init."""
    if pshapes is None:
        from repro.models import init_lm

        pshapes = jax.eval_shape(lambda: init_lm(jax.random.key(0), cfg))
    return jtu.tree_map_with_path(
        lambda path, leaf: _param_leaf_axes(path, leaf.ndim), pshapes
    )


def opt_axes(paxes) -> Any:
    """Axes for the AdamW state: moments mirror the params, step is scalar."""
    return {"m": paxes, "v": paxes, "step": ()}


# ---------------------------------------------------------------- inputs
def batch_axes(cfg, kind: str) -> Any:
    """Logical axes for ``configs.input_specs(cfg, shape)`` of each kind."""
    if kind in ("train", "prefill"):
        if cfg.family == "vlm":
            axes = {
                "embeds": ("batch", "seq", "act_embed"),
                "positions": ("batch", "seq", "none"),
            }
            if kind == "train":
                axes["labels"] = ("batch", "seq")
            return axes
        if cfg.n_codebooks:
            return {"tokens": ("batch", "seq", "none")}
        return {"tokens": ("batch", "seq")}
    # decode: one token against a cache
    tok = ("batch", "none", "none") if cfg.n_codebooks else ("batch", "none")
    return {"tokens": tok, "cache": cache_axes(cfg), "cache_len": ()}


def cache_axes(cfg) -> Any:
    """Logical axes for ``models.make_cache(cfg, ...)``. The KV sequence dim
    takes `model` (sequence-sharded decode cache), which conflict-resolves
    kv_heads to replicated."""
    from repro.models.transformer import n_attn_caches

    axes: Dict[str, Axes] = {}
    if n_attn_caches(cfg):
        kv = ("layer", "batch", "cache_seq", "kv_heads", "none")
        axes["k"] = kv
        axes["v"] = kv
    if cfg.family in ("ssm", "hybrid"):
        axes["conv_x"] = ("layer", "batch", "conv", "d_inner")
        axes["conv_B"] = ("layer", "batch", "conv", "none")
        axes["conv_C"] = ("layer", "batch", "conv", "none")
        axes["ssm"] = ("layer", "batch", "heads", "none", "none")
    return axes


# ----------------------------------------------------------- activations
def ambient_mesh():
    """The mesh set by ``jax.sharding.set_mesh``, or None outside one
    (``get_abstract_mesh`` then returns an empty mesh)."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.shape else None


def shard_act(x, *axes: str):
    """Shard an activation as the rule engine resolves its logical axes.

    Safely a no-op when called outside any mesh context (unit tests, eager
    CPU runs) — model code calls this unconditionally from scan bodies.
    Under a mesh whose axes are all ``Explicit`` (the default of
    ``jax.make_mesh``) ``with_sharding_constraint`` only asserts the
    sharding, so the array is moved with ``reshard`` instead."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    # a typo'd logical axis or rank mismatch is a caller bug and must raise,
    # not silently drop the constraint
    sharding = NamedSharding(mesh, spec_for(tuple(x.shape), axes, mesh))
    if all(t == jax.sharding.AxisType.Explicit for t in mesh.axis_types):
        return jax.sharding.reshard(x, sharding)
    return jax.lax.with_sharding_constraint(x, sharding)
