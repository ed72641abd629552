"""Sharding rules: parameter/activation pytrees -> PartitionSpecs.

Strategy (DESIGN.md Sec 4):

* ``data`` mesh axis = DP + FSDP: every weight is additionally sharded over
  'data' on its d_model-ish dimension (ZeRO-3 via GSPMD — XLA inserts the
  per-layer all-gathers under the layer scan).
* ``model`` mesh axis = TP/EP: heads / ffn / expert dimensions.
* ``pod`` mesh axis (multi-pod) = extra pure-DP dimension; the batch is
  sharded over ('pod', 'data') jointly.

All assignments are divisibility-checked per tensor; a dimension that does
not divide simply stays unsharded (e.g. gemma3's 4 query heads on a 16-way
'model' axis fall back to replicated heads with sharded d_model), so every
architecture lowers on every mesh without bespoke per-arch rules.
"""

from __future__ import annotations

import os
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Preferred (mesh_axis -> tensor dim chooser) per parameter leaf name.
# Dims are indexed AFTER stripping the leading layer-stack dimension.
# Each entry: list of (dim, mesh_axis) preferences tried in order.
_NAME_RULES: dict[str, list[tuple[int, str]]] = {
    # (V, d)
    "embed": [(0, "model"), (1, "data")],
    # (d, V)
    "unembed": [(1, "model"), (0, "data")],
    # attention: (d, H, Dh) / (H, Dh, d)
    "wq": [(1, "model"), (0, "data")],
    "wk": [(1, "model"), (0, "data")],
    "wv": [(1, "model"), (0, "data")],
    # (d, f) mlp in / (f, d) mlp out — also matches attn wo (H, Dh, d) via
    # ndim dispatch below
    "wi_gate": [(1, "model"), (0, "data")],
    "wi_up": [(1, "model"), (0, "data")],
    # ssm
    "in_proj": [(1, "model"), (0, "data")],
    "out_proj": [(0, "model"), (1, "data")],
    "x_proj": [(0, "model")],
    "dt_proj": [(1, "model")],
    "conv_w": [(1, "model")],
    "conv_b": [(0, "model")],
    "A_log": [(0, "model")],
    "D": [(0, "model")],
    # moe: router (d, E); expert weights (E, d, f) / (E, f, d)
    "router": [(0, "data")],
    # media
    "media_proj": [(1, "model"), (0, "data")],
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _path_has(path, *names) -> bool:
    keys = {str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)}
    return any(n in keys for n in names)


def _stacked(path) -> bool:
    """Leaves under blocks/moe_blocks/cross_blocks/shared_attn/attn_blocks
    carry a leading layer-stack dimension that must never be sharded (scan
    axis)."""
    return _path_has(path, "blocks", "moe_blocks", "cross_blocks",
                     "shared_attn", "attn_blocks")


def param_spec(path, shape: tuple[int, ...], mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf."""
    axes = dict(zip(mesh.axis_names, mesh.shape.values()))
    tp = axes.get("model", 1)
    dp = axes.get("data", 1)
    off = 1 if _stacked(path) else 0
    dims = shape[off:]
    spec: list[Any] = [None] * len(shape)

    name = _leaf_name(path)
    used_axes: set[str] = set()

    def try_assign(dim: int, axis: str) -> None:
        size = {"model": tp, "data": dp}[axis]
        d = dim + off
        if (axis not in used_axes and d < len(shape) and spec[d] is None
                and shape[d] % size == 0 and size > 1):
            spec[d] = axis
            used_axes.add(axis)

    # moe expert tensors: EP if expert count divides, else TP on ffn dim
    if name in ("wi_gate", "wi_up", "wo") and len(dims) == 3 and \
            _path_has(path, "moe"):
        E, a, b = dims
        # REPRO_MOE_TP=1 forces TP-on-ffn expert sharding even when the
        # expert count divides (the EP scatter-dispatch path makes GSPMD
        # gather the full token set; see EXPERIMENTS.md §Perf iteration 5)
        if E % tp == 0 and not os.environ.get("REPRO_MOE_TP"):
            try_assign(0, "model")
            try_assign(1, "data")
        else:
            ff_dim = 2 if name != "wo" else 1
            try_assign(ff_dim, "model")
            try_assign(1 if name != "wo" else 2, "data")
    elif name == "wo" and len(dims) == 3:         # attn wo: (H, Dh, d)
        try_assign(0, "model")
        try_assign(2, "data")
    elif name == "wo" and len(dims) == 2:         # mlp wo: (f, d)
        try_assign(0, "model")
        try_assign(1, "data")
    elif name in _NAME_RULES:
        for dim, axis in _NAME_RULES[name]:
            try_assign(dim, axis)
    else:
        # generic fallback: biggest dim -> model, next -> data
        order = sorted(range(len(dims)), key=lambda i: -dims[i])
        if order:
            try_assign(order[0], "model")
        if len(order) > 1:
            try_assign(order[1], "data")
    return P(*spec)


def param_shardings(params_shape: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree matching an eval_shape(init) result."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, param_spec(path, leaf.shape, mesh)), params_shape)


# --- batch / activations / cache -------------------------------------------------

def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh: Mesh, global_batch: int) -> P:
    axes = batch_axes(mesh)
    size = 1
    for a in axes:
        size *= dict(zip(mesh.axis_names, mesh.shape.values()))[a]
    if axes and global_batch % size == 0:
        return P(axes)
    return P()


def batch_shardings(batch_shape: Any, mesh: Mesh, global_batch: int) -> Any:
    spec = batch_spec(mesh, global_batch)
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, spec if leaf.shape and leaf.shape[0] == global_batch
            else P()), batch_shape)


def cache_spec(path, shape: tuple[int, ...], mesh: Mesh,
               batch_size: int) -> P:
    """Decode-cache leaf sharding: batch over data axes; heads/channels over
    model; for unshardable batch (e.g. long_500k B=1) shard the sequence
    dimension of KV over 'data' instead."""
    axes = dict(zip(mesh.axis_names, mesh.shape.values()))
    tp = axes.get("model", 1)
    dsize = 1
    for a in batch_axes(mesh):
        dsize *= axes[a]
    name = _leaf_name(path)
    spec: list[Any] = [None] * len(shape)
    if name in ("k", "v", "media_k", "media_v"):
        # (L, B, S, K, Dh)
        if shape[1] % dsize == 0 and dsize > 1:
            spec[1] = batch_axes(mesh)
        elif shape[2] % dsize == 0 and dsize > 1:
            spec[2] = batch_axes(mesh)          # sequence-sharded KV
        if shape[3] % tp == 0 and tp > 1:
            spec[3] = "model"
        elif spec[2] is None and shape[2] % tp == 0 and tp > 1:
            spec[2] = "model"
    elif name in ("conv", "h"):
        if shape[1] % dsize == 0 and dsize > 1:
            spec[1] = batch_axes(mesh)
        for d in range(len(shape) - 1, 1, -1):
            if shape[d] % tp == 0 and tp > 1:
                spec[d] = "model"
                break
    return P(*spec)


def cache_shardings(cache_shape: Any, mesh: Mesh, batch_size: int) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_spec(path, leaf.shape, mesh, batch_size)
            if leaf.ndim > 0 else P()), cache_shape)


def activation_spec(mesh: Mesh) -> P:
    """(B, T, D) residual-stream constraint: batch over data, seq over model
    (sequence parallelism between blocks)."""
    return P(batch_axes(mesh) or None, "model" if "model" in
             mesh.axis_names else None, None)
