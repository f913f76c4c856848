"""Sharding rules: params / optimizer / batches / decode caches onto the
production mesh.

Axes (DESIGN.md §6):
  ``model`` — TP: q-heads, ffn, vocab, experts (EP), SSM inner dim; and the
              KV-cache *sequence* axis for decode cells whose kv-head count
              does not divide the TP degree (context-parallel decode, served
              by :func:`repro.models.attention.decode_attention`).
  ``data``  — DP for batches; ZeRO-1 axis for optimizer moments.
  ``pod``   — second DP axis on the multi-pod mesh.  PP could claim this
              axis (the rules only touch ``data``/``model`` for params), but
              at TP=16 × DP=32 the pipeline is not needed for the assigned
              configs.

Every rule is divisibility-guarded: a dimension that does not divide evenly
by the mesh axis falls back to replication for that dimension, so the same
rule set serves full configs, smoke configs, and single-device tests.
"""

from __future__ import annotations

import math
import re
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Mesh helpers
# ---------------------------------------------------------------------------


def dp_axes(mesh: Mesh) -> tuple:
    """Data-parallel axes: ('pod', 'data') on the multi-pod mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def abstract_mesh(sizes, names):
    """Device-free mesh for planning/routing decisions.  The serving
    ShardedBackend uses this to consult :func:`cache_pspecs` for KV-head
    vs sequence routing without touching device state."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(sizes), tuple(names))


def axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def _fit(spec: Sequence, shape: tuple, mesh: Mesh) -> P:
    """Right-align ``spec`` against ``shape`` (leading stacked axes get None)
    and drop any axis whose size does not divide the dimension."""
    spec = tuple(spec)
    assert len(spec) <= len(shape), (spec, shape)
    full = (None,) * (len(shape) - len(spec)) + spec
    out = []
    for dim, ax in zip(shape, full):
        if ax is None:
            out.append(None)
        elif dim % axes_size(mesh, ax) == 0 and axes_size(mesh, ax) > 0:
            out.append(ax)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# Ordered (path regex, trailing spec). Specs are *trailing*: leading stacked
# depth axes (scan layers, zamba segments) are padded with None by _fit.
_PARAM_RULES = [
    # MoE expert banks — EP shards the expert axis, TP shards the ffn dim.
    (r"moe/(w_gate|w_in)$", {"ep": ("model", None, None), "tp": (None, None, "model")}),
    (r"moe/w_out$", {"ep": ("model", None, None), "tp": (None, "model", None)}),
    (r"moe/router$", {"*": (None, None)}),
    (r"moe/shared/(w_gate|w_in)$", {"*": (None, "model")}),
    (r"moe/shared/w_out$", {"*": ("model", None)}),
    # Dense MLP.
    (r"mlp/(w_gate|w_in)$", {"*": (None, "model")}),
    (r"mlp/w_out$", {"*": ("model", None)}),
    # Attention (grouped-GQA layout: q-head axis shards).  kv projections
    # fall back to row-parallel d-axis sharding when n_kv_heads < TP — the
    # projection gains a (small) psum but the 0.5–1 GB/device of replicated
    # kv weights disappears (candidate list: first spec whose 'model' axis
    # survives divisibility wins).
    (r"/wq$", {"*": (None, "model", None)}),
    (r"/(wk|wv)$", {"*": [(None, "model", None), ("model", None, None)]}),
    (r"/wo$", {"*": ("model", None, None)}),
    # Mamba2 / SSD: inner dim (= heads×head_dim) shards.
    (r"ssm/(wz|wx)$", {"*": (None, "model")}),
    (r"ssm/conv_x$", {"*": (None, "model")}),
    (r"ssm/(wb|wc|wdt|conv_b|conv_c)$", {"*": (None, None)}),
    (r"ssm/(a_log|d_skip|dt_bias)$", {"*": (None,)}),
    (r"ssm/norm/scale$", {"*": ("model",)}),
    (r"ssm/w_out$", {"*": ("model", None)}),
    # Embedding / head: vocab-parallel.
    (r"embed/table$", {"*": ("model", None)}),
    (r"lm_head/w$", {"*": ("model", None)}),
    (r"patch_proj$", {"*": (None, "model")}),
    # Norm scales and anything else: replicate.
    (r".*", {"*": ()}),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_pspecs(cfg, params_tree, mesh: Mesh, mode: str = "tp"):
    """PartitionSpec tree for a params (or grads) tree of arrays/specs.

    mode (the §Perf mesh-mapping knob):
      'tp'   — tensor parallel over 'model' (default; the rules below)
      'fsdp' — same param sharding, but the batch ALSO shards over 'model'
               (see batch_pspecs): GSPMD then all-gathers weights per layer
               instead of all-reducing activations — ZeRO-3 semantics
      'dp'   — replicate params, shard batch over every axis (small models)
    """
    shard_kind = getattr(cfg, "expert_shard", "tp")

    def rule(path, leaf):
        if getattr(cfg, "replicate_weights", False) or mode == "dp":
            return P()
        p = _path_str(path)
        for pat, by_kind in _PARAM_RULES:
            if re.search(pat, p):
                spec = by_kind.get(shard_kind, by_kind.get("*"))
                if isinstance(spec, list):  # candidates: first that shards
                    for cand in spec:
                        fitted = _fit(cand, leaf.shape, mesh)
                        if any(ax is not None for ax in tuple(fitted)):
                            return fitted
                    return _fit(spec[0], leaf.shape, mesh)
                return _fit(spec, leaf.shape, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def opt_pspecs(cfg, opt_tree, param_specs, mesh: Mesh):
    """ZeRO-1: moments take the param spec plus 'data' on the first free,
    divisible dimension.  'step' (and any scalar) stays replicated."""

    def zero1(spec: P, leaf):
        if leaf.ndim == 0:
            return P()
        parts = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, (dim, ax) in enumerate(zip(leaf.shape, parts)):
            if ax is None and dim % (axes_size(mesh, "data") or 1) == 0 and dim > 1:
                if "data" in mesh.axis_names:
                    parts[i] = "data"
                break
        return P(*parts)

    def rule(path, leaf):
        p = _path_str(path)
        if p.startswith("m/") or p.startswith("v/"):
            sub = p.split("/", 1)[1]
            pspec = _lookup_by_path(param_specs, sub)
            return zero1(pspec, leaf)
        return P()

    return jax.tree_util.tree_map_with_path(rule, opt_tree)


def _lookup_by_path(tree, path: str):
    node = tree
    for part in path.split("/"):
        if isinstance(node, dict):
            node = node[part]
        elif isinstance(node, (list, tuple)):
            node = node[int(part)]
        else:
            raise KeyError(path)
    return node


# ---------------------------------------------------------------------------
# Batch / cache rules
# ---------------------------------------------------------------------------


def batch_pspecs(cfg, batch_tree, mesh: Mesh, mode: str = "tp"):
    """Leading axis = global batch, sharded over the DP axes ('fsdp'/'dp'
    modes additionally claim the 'model' axis for the batch)."""
    dp = dp_axes(mesh)
    if mode in ("fsdp", "dp"):
        dp = dp + ("model",)

    def rule(leaf):
        if leaf.ndim == 0:
            return P()
        return _fit((dp,) + (None,) * (leaf.ndim - 1), leaf.shape, mesh)

    return jax.tree.map(rule, batch_tree)


def _kv_spec(shape, mesh: Mesh, batch_axis: int = 1) -> P:
    """(..., B, S, Hkv, hd): prefer kv-head TP sharding (comm-free decode);
    fall back to sequence sharding (context-parallel decode via the
    all-reduce softmax in decode_attention); else replicate S."""
    dp = dp_axes(mesh)
    nd = len(shape)
    s_dim, h_dim = nd - 3, nd - 2
    parts = [None] * nd
    parts[batch_axis] = dp
    m = axes_size(mesh, "model")
    if shape[h_dim] % m == 0:
        parts[h_dim] = "model"
    elif shape[s_dim] % m == 0:
        parts[s_dim] = "model"
    return _fit(parts, shape, mesh)


def cache_pspecs(cfg, cache_tree, mesh: Mesh):
    """Decode-cache sharding per family (see module docstring)."""
    fam = cfg.family
    kv_names = {"k", "v", "self_k", "self_v", "cross_k", "cross_v"}

    def rule(path, leaf):
        name = _path_str(path)
        tail = name.rsplit("/", 1)[-1]
        if leaf.ndim == 0 or tail == "len":
            return P()
        if tail in ("sk", "sv") and leaf.ndim == 5:
            # staging ring: tiny — batch-sharded only, S replicated
            return _fit((None, dp_axes(mesh), None, None, None), leaf.shape, mesh)
        if tail in kv_names and leaf.ndim == 5:
            return _kv_spec(leaf.shape, mesh)
        if tail == "pos":  # ring-position array mirrors the k/v S sharding
            k_shape = _sibling_shape(cache_tree, name, "k")
            kspec = _kv_spec(k_shape, mesh)
            return P(*(list(kspec)[:2] + [kspec[2]]))
        if fam in ("ssm", "hybrid"):
            # SSM state leaves: trailing dims include the inner/head dims.
            if tail == "state":  # (..., B, H, N, P): shard H
                return _fit((dp_axes(mesh), "model", None, None), leaf.shape, mesh)
            if tail == "conv_x":  # (..., B, W-1, din): shard din
                return _fit((dp_axes(mesh), None, "model"), leaf.shape, mesh)
            if tail in ("conv_b", "conv_c"):
                return _fit((dp_axes(mesh), None, None), leaf.shape, mesh)
        return P()

    def _sibling_shape(tree, name, sib):
        prefix = name.rsplit("/", 1)[0] if "/" in name else ""
        path = f"{prefix}/{sib}" if prefix else sib
        return _lookup_by_path(tree, path).shape

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def named(mesh: Mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
