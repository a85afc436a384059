"""Activation-sharding hints.

`hint(x, *axes)` applies `with_sharding_constraint` using the ambient
mesh (`jax.set_mesh`), silently no-oping when there is no mesh (unit
tests, single-device runs) or when an axis does not divide the
corresponding dim. Axis entries may be:
  * None            — unsharded dim
  * "data"/"model"  — mesh axis (dropped if absent/non-dividing)
  * "batch"         — expands to the (pod, data) data-parallel axes

The layer library calls `attn_qkv_hint` which picks the memory-safe
layout per arch: heads over model when head count divides the TP size
(Megatron), else query-sequence over model (context/sequence parallel —
the qwen/starcoder/minitron/whisper head counts don't divide 16; see
EXPERIMENTS.md §Perf iteration 1).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


def _mesh():
    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.axis_names else None


def _expand(ax, mesh):
    if ax == "batch":
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return axes if axes else None
    if isinstance(ax, str) and ax not in mesh.axis_names:
        return None
    return ax


def hint(x, *axes) -> jax.Array:
    mesh = _mesh()
    if mesh is None:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        ax = _expand(ax, mesh)
        if ax is None:
            spec.append(None)
            continue
        group = ax if isinstance(ax, tuple) else (ax,)
        size = int(np.prod([mesh.shape[a] for a in group]))
        spec.append(ax if dim % size == 0 else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*spec))
    except Exception:   # no-mesh or partitioning corner: stay unhinted
        return x


def tp_size() -> int:
    mesh = _mesh()
    return mesh.shape.get("model", 1) if mesh is not None else 1


def dp_size() -> int:
    """Total data-parallel ways (pod x data)."""
    mesh = _mesh()
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape.get(a, 1) for a in ("pod", "data")]))


def attn_layout(n_heads: int, seq: int) -> str:
    """'heads' (Megatron TP) when divisible, else 'seq' (context
    parallel), else 'none'."""
    tp = tp_size()
    if tp == 1:
        return "none"
    if n_heads % tp == 0:
        return "heads"
    if seq % tp == 0:
        return "seq"
    return "none"


def hint_qkv(q, k, v, layout: str):
    """q/k/v are [B, S, H|KVH, D]."""
    if layout == "heads":
        q = hint(q, "batch", None, "model", None)
        # kv heads may not divide (GQA kv=8 < tp=16): hint fits per-dim
        k = hint(k, "batch", None, "model", None)
        v = hint(v, "batch", None, "model", None)
    elif layout == "seq":
        q = hint(q, "batch", "model", None, None)
        k = hint(k, "batch", None, None, None)
        v = hint(v, "batch", None, None, None)
    return q, k, v


def hint_attn_out(o, layout: str):
    """o is [B, S, H, D] pre-reshape."""
    if layout == "heads":
        return hint(o, "batch", None, "model", None)
    if layout == "seq":
        return hint(o, "batch", "model", None, None)
    return o
