"""Partition-spec rules for every parameter / batch / cache tree.

Philosophy: megatron-style tensor parallelism over the 'model' axis,
batch-like axes over ('pod','data'). Rules are path+shape based and
left-padded with None for stacked (scan) leading axes, so the same rule
covers a single block and an (L, ...) stack.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in batch_axes(mesh)], dtype=np.int64))


def tp_size(mesh: Mesh) -> int:
    return int(mesh.shape.get("model", 1))


def _pad(spec: tuple, ndim: int) -> P:
    return P(*((None,) * (ndim - len(spec)) + spec))


def fit_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop sharded axes whose dimension isn't divisible by the axis size —
    ``jit in_shardings`` requires exact divisibility (granite's vocab 49155
    and hubert's 504 otherwise reject the vocab-parallel spec)."""
    out = []
    for i, entry in enumerate(tuple(spec)):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))
        out.append(entry if size and shape[i] % size == 0 else None)
    return P(*out)


def param_spec(path: tuple[str, ...], leaf, cfg: ModelConfig, tp: int) -> P:
    """Spec for one parameter leaf. ``path`` is the tuple of dict keys."""
    name = path[-1]
    joined = "/".join(path)
    nd = leaf.ndim

    # --- embeddings / head ------------------------------------------------
    if name == "embed":
        return P("model", None)                       # vocab-parallel
    if name == "head":
        return P(None, "model")

    # --- MoE (leaf rank 3 base: (E, D, F) / (E, F, D)) ---------------------
    # F-axis sharding uniformly (works for E=40 and E=64 alike) and matches
    # the shard_map combine-before-psum layout in models/moe.py. Pure EP
    # (expert-axis sharding + a2a dispatch) is a further §Perf lever.
    if cfg.n_experts and "ffn" in path and name in ("wi", "wg", "wo"):
        if name in ("wi", "wg"):
            base = (None, None, "model")
        else:
            base = (None, "model", None)
        return _pad(base, nd)
    if name == "router":
        return _pad((None, None), nd)

    # --- attention (head-major: wq (D,H,dh), wo (H,dh,D)) -------------------
    if name == "wq":
        return _pad((None, "model", None), nd)        # shard the head axis
    if name in ("wk", "wv", "bk", "bv"):
        return _pad((), nd)                           # KV replicated (GQA)
    if name == "bq":
        return _pad(("model", None), nd)
    if name == "wo" and "attn" in path:
        return _pad(("model", None, None), nd)        # heads row-parallel

    # --- dense / recurrent mlps ---------------------------------------------
    if name in ("wi", "wg", "in_proj", "Wr", "Wk", "Wv", "Wg", "conv_w",
                "wA"):
        if "cmix" in path and name == "Wv":           # (F, D) row-parallel
            return _pad(("model", None), nd)
        return _pad((None, "model"), nd)              # column-parallel
    if name in ("wo", "out_proj", "Wo"):
        return _pad(("model", None), nd)              # row-parallel
    if name == "wB":                                   # rwkv decay lora out
        return _pad((None, None), nd)
    if name == "w" and "pos_conv" in path:
        return _pad((None, None, "model"), nd)

    # everything else (norms, scalars, biases, mus) replicated
    return _pad((), nd)


def param_shardings(mesh: Mesh, cfg: ModelConfig, params_tree):
    tp = tp_size(mesh)

    def to_sharding(path, leaf):
        keys = tuple(p.key for p in path)
        spec = fit_spec(param_spec(keys, leaf, cfg, tp), tuple(leaf.shape),
                        mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(to_sharding, params_tree)


def batch_spec(mesh: Mesh, ndim: int, batch_size: int) -> P:
    """Token-like arrays: leading batch dim over ('pod','data') if divisible."""
    ax = batch_axes(mesh)
    if ax and batch_size % data_size(mesh) == 0:
        return P(ax, *([None] * (ndim - 1)))
    return P(*([None] * ndim))


def cache_spec(path: tuple[str, ...], leaf, mesh: Mesh, cfg: ModelConfig,
               batch: int) -> P:
    """KV caches / recurrent states for decode."""
    name = path[-1]
    nd = leaf.ndim
    ax = batch_axes(mesh)
    b_ok = ax and batch % data_size(mesh) == 0
    tp = tp_size(mesh)
    bspec = ax if b_ok else None

    if name in ("k", "v"):                   # (L|G, B, S, Hkv, Dh)
        if b_ok:
            return P(None, bspec, "model", None, None)
        # batch too small (long-context): shard the sequence everywhere
        seq_ax = tuple(ax) + ("model",)
        return P(None, None, seq_ax, None, None)
    if name == "h":                          # (L, B, H, dh, ds)
        h_ax = "model" if leaf.shape[2] % tp == 0 else None
        return P(None, bspec, h_ax, None, None)
    if name == "S":                          # (L, B, H, N, N)
        h_ax = "model" if leaf.shape[2] % tp == 0 else None
        return P(None, bspec, h_ax, None, None)
    if name == "conv":                       # (L, B, K, C)
        return P(None, bspec, None, "model" if leaf.shape[3] % tp == 0 else None)
    if name in ("tmix_x", "cmix_x"):         # (L, B, 1, D)
        return P(None, bspec, None, None)
    if name == "pos":
        return P()
    return P(*([None] * nd))


def cache_shardings(mesh: Mesh, cfg: ModelConfig, cache_tree, batch: int):
    def to_sharding(path, leaf):
        keys = tuple(p.key for p in path)
        spec = fit_spec(cache_spec(keys, leaf, mesh, cfg, batch),
                        tuple(leaf.shape), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(to_sharding, cache_tree)
