"""Sharding rules: path/shape-pattern → PartitionSpec, per family.

Megatron-style tensor parallelism on the ``model`` axis + optional ZeRO-3
FSDP on the ``data`` axis; the ``pod`` axis is pure data parallelism (its
gradient sync is the paper-relevant slow link, optionally TT-compressed).

The rules operate on the *path names* of the parameter pytree (NamedTuple
field names), so one table covers every architecture:

  attention   wq (L,D,H,Dh)→ heads on model     wo (L,H,Dh,D)→ heads on model
  mlp         w_gate/w_up (L,D,F)→ F on model   w_down (L,F,D)→ F on model
  moe         experts (L,E,D,F)→ E on model (EP)
  mamba/rglru inner width on model
  embeddings  vocab on model
  norms/bias  replicated (tiny)

FSDP (when cfg.fsdp) additionally shards the non-model embed/hidden dim of
big tensors over ``data``.
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# (path regex, ndim -> PartitionSpec builder).  {m}=model axis, {f}=fsdp axis.
# Specs written for the LAYER-STACKED tensors (leading L axis) — the leading
# None is dropped automatically for unstacked tensors of rank-1 lower.
_RULES = [
    # --- embeddings / unembeddings: (V, D) ---
    (r"(embed|lm_head)$", lambda m, f: P(m, f)),
    # --- attention ---
    (r"attn\.wq$|self_attn\.wq$|cross_attn\.wq$", lambda m, f: P(None, f, m, None)),
    (r"attn\.wk$|self_attn\.wk$|cross_attn\.wk$", lambda m, f: P(None, f, m, None)),
    (r"attn\.wv$|self_attn\.wv$|cross_attn\.wv$", lambda m, f: P(None, f, m, None)),
    (r"attn\.wo$|self_attn\.wo$|cross_attn\.wo$", lambda m, f: P(None, m, None, f)),
    (r"attn\.b[qkv]$", lambda m, f: P(None, m, None)),
    (r"attn\.(q|k)_norm$", lambda m, f: P(None, None)),
    # --- dense MLP ---
    (r"mlp\.w_gate$|mlp\.w_up$", lambda m, f: P(None, f, m)),
    (r"mlp\.w_down$", lambda m, f: P(None, m, f)),
    # --- MoE (expert parallel) ---
    (r"moe\.router$", lambda m, f: P(None, f, None)),
    (r"moe\.w_gate$|moe\.w_up$", lambda m, f: P(None, m, f, None)),
    (r"moe\.w_down$", lambda m, f: P(None, m, None, f)),
    # --- Mamba-2 ---
    (r"\.w_in$", lambda m, f: P(None, f, m)),
    (r"\.conv_w$", lambda m, f: P(None, None, m)),
    (r"\.conv_b$", lambda m, f: P(None, m)),
    (r"\.(a_log|d_skip|dt_bias)$", lambda m, f: P(None, m)),
    (r"\.gate_norm$", lambda m, f: P(None, m)),
    (r"\.w_out$", lambda m, f: P(None, m, f)),
    # --- RG-LRU ---
    (r"\.w_x$|\.w_gate$", lambda m, f: P(None, f, m)),
    (r"\.(lam|b_rg|b_ig)$", lambda m, f: P(None, m)),
    (r"\.w_rg$|\.w_ig$", lambda m, f: P(None, None, m)),
    # --- norms ---
    (r"(ln\d?|ln_x|final_norm|enc_norm|ln)$", lambda m, f: P(None, None)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return ".".join(parts)


def spec_for(path: str, shape, cfg, model_axis="model",
             fsdp_axis="data") -> P:
    """PartitionSpec for one parameter."""
    f_ax = fsdp_axis if cfg.fsdp else None
    if getattr(cfg, "opt_moe_tp", False) and re.search(r"moe\.w_", path):
        # §Perf (dbrx): TP-sharded experts — d_ff on the model axis, experts
        # replicated; the FFN contraction then needs a single (cap, D)
        # all-reduce rather than d_ff-wide partial sums.
        if re.search(r"moe\.w_gate$|moe\.w_up$", path):      # (L,E,D,F)
            return _fit(P(None, None, f_ax, model_axis), len(shape), shape)
        if re.search(r"moe\.w_down$", path):                  # (L,E,F,D)
            return _fit(P(None, None, model_axis, f_ax), len(shape), shape)
    for pat, builder in _RULES:
        if re.search(pat, path):
            spec = builder(model_axis, f_ax)
            spec = _fit(spec, len(shape), shape)
            return spec
    # default: replicate
    return P(*([None] * len(shape)))


def _fit(spec: P, ndim: int, shape) -> P:
    """Adapt a stacked-layer spec to the actual rank and drop axes that do
    not divide the dimension."""
    parts = list(spec)
    if len(parts) == ndim + 1 and parts[0] is None:
        parts = parts[1:]                      # unstacked variant
    while len(parts) < ndim:
        parts.append(None)
    parts = parts[:ndim]
    # divisibility guard: never emit a spec a dim can't honor
    out = []
    for dim, ax in zip(shape, parts):
        if ax is None:
            out.append(None)
            continue
        size = _axis_size(ax)
        out.append(ax if size is not None and dim % size == 0 else None)
    return P(*out)


_MESH_SIZES = {}
_CURRENT_MESH = None


def set_mesh_axis_sizes(mesh: Mesh):
    global _MESH_SIZES, _CURRENT_MESH
    _MESH_SIZES = dict(zip(mesh.axis_names, mesh.devices.shape))
    _CURRENT_MESH = mesh


def current_mesh() -> Optional[Mesh]:
    """The mesh registered by the launcher (for explicit shard_map regions)."""
    return _CURRENT_MESH


def _axis_size(ax) -> Optional[int]:
    if isinstance(ax, tuple):
        sizes = [_MESH_SIZES.get(a) for a in ax]
        if any(s is None for s in sizes):
            return None
        return int(np.prod(sizes))
    return _MESH_SIZES.get(ax)


def param_specs(params_shape, cfg, model_axis="model", fsdp_axis="data"):
    """PartitionSpec pytree matching a params (shape) pytree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = [
        spec_for(_path_str(path), leaf.shape, cfg, model_axis, fsdp_axis)
        for path, leaf in flat
    ]
    return jax.tree_util.tree_unflatten(treedef, specs)


def place_params(params, cfg, mesh: Mesh):
    """``device_put`` a params pytree onto ``mesh`` under the rules above.

    TT-native leaves (``TTLinear``) are replicated whole: no rule shards a
    TT chain, and their cores are the compressed payload.  Dense leaves
    (the embedding included) take their ``spec_for`` sharding."""
    from repro.core.tt_linear import is_tt_linear

    def one(path, leaf):
        if is_tt_linear(leaf):
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        spec = spec_for(_path_str(path), leaf.shape, cfg)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=is_tt_linear)


def batch_specs(batch_shape, data_axes=("pod", "data")):
    """Batch pytree: leading dim over (pod, data); embeddings stubs too.
    Dims that don't divide the axis product (e.g. batch=1 at long_500k)
    fall back to replication via the _fit guard."""
    def one(leaf):
        nd = len(leaf.shape)
        return _fit(P(data_axes, *([None] * (nd - 1))), nd, leaf.shape)
    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape, cfg, data_axes=("pod", "data"),
                model_axis="model"):
    """Decode-cache sharding: batch over data axes; the long sequence axis
    over the model axis (flash-decode/sequence-parallel, DESIGN.md §4);
    recurrent states shard their width over model."""
    def one(path, leaf):
        name = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        if name.endswith("pos"):
            return P()
        if name in ("k", "v", "mem_k", "mem_v"):
            # (L, B, S, Hkv, Dh): batch over data, seq over model
            spec = P(None, data_axes, model_axis, None, None)
            return _fit(spec, nd, shape)
        if "conv" in name:
            return _fit(P(None, data_axes, None, model_axis), nd, shape)
        if name.startswith("h") or name == "ssm_state":
            # recurrent state: (L, B, R) / (L, B, H, N, P)
            spec = P(None, data_axes, model_axis, None, None)
            return _fit(spec, nd, shape)
        return _fit(P(None, data_axes), nd, shape)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    specs = [one(path, leaf) for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def act_constraint(x, *axes):
    """with_sharding_constraint for ACTIVATIONS, tolerant of absent axes.

    axes: one mesh-axis name (or None) per dim of x.  Axes missing from the
    current mesh, or not dividing the dim, are dropped (no-op on host
    meshes) — so model code can state intent unconditionally.
    """
    parts = []
    for dim, ax in zip(x.shape, axes):
        size = _axis_size(ax) if ax is not None else None
        parts.append(ax if (size and dim % size == 0 and size > 1) else None)
    if all(p is None for p in parts):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, P(*parts))
    except (ValueError, RuntimeError):
        return x                     # no mesh in context (plain jit)
