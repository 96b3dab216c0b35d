"""Sharding-rule helpers shared by the trainer and the server.

A *rule* is an ordered list of (dim, axes) pairs: "try to shard dimension
`dim` over the mesh axes `axes`".  `_assign` applies the first rules whose
dimension is divisible by the axes' total size (GSPMD can pad uneven shards,
but we only do that when explicitly asked via allow_uneven — e.g. the
head-padding perf toggle, where padded heads are output-masked so the
computation stays exact).
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes]))


def _assign(shape, rules, mesh: Mesh, allow_uneven: bool = False) -> P:
    """PartitionSpec for `shape` from ordered (dim, axes) rules.

    A rule fires when the dimension is divisible by the axes' product size, or
    when allow_uneven=True and the dimension is at least that size (GSPMD pads
    the ragged last shard).  Each mesh axis and each dimension is used at most
    once; unmatched dimensions stay replicated.
    """
    spec: list = [None] * len(shape)
    used: set[str] = set()
    for dim, axes in rules:
        d = dim if dim >= 0 else len(shape) + dim
        if d < 0 or d >= len(shape) or spec[d] is not None:
            continue
        if any(a in used for a in axes):
            continue
        size = _axes_size(mesh, axes)
        if size <= 1:
            continue
        if shape[d] % size != 0 and not (allow_uneven and shape[d] >= size):
            continue
        spec[d] = axes[0] if len(axes) == 1 else tuple(axes)
        used.update(axes)
    return P(*spec)


# RoPE splits/concats the trailing head_dim of q/k, and XLA:CPU's SPMD
# partitioner miscompiles that pattern when head_dim is sharded (verified:
# O(1) absolute error vs replicated).  Keep every dim that RoPE touches — the
# last dim of the q/k/v projections and biases — replicated.
_ROPE_LAST_DIM_KEYS = frozenset({"wq", "wk", "wv", "bq", "bk", "bv"})


def _leaf_key(path) -> str:
    for entry in reversed(path):
        key = getattr(entry, "key", getattr(entry, "name", None))
        if key is not None:
            return str(key)
    return ""


def _candidate_dims(shape, start: int, leaf_key: str):
    """Shardable inner dims, largest first; drops the RoPE head_dim."""
    dims = list(range(start, len(shape)))
    if leaf_key in _ROPE_LAST_DIM_KEYS and len(dims) > 1:
        dims = dims[:-1]
    return sorted(dims, key=lambda i: -shape[i])


def leaf_train_spec(shape, mesh: Mesh, allow_uneven: bool = False,
                    leaf_key: str = "") -> P:
    """Spec for one stacked trainer leaf (W, ...): worker on dim 0, the
    largest remaining dim FSDP-sharded, the next largest tensor-parallel.
    The trainer's owner slots (W * C, ...) take it too: 'worker' splits
    their rows into W blocks of C, one per worker."""
    if len(shape) == 0:
        return P()
    order = _candidate_dims(shape, 1, leaf_key)
    rules = [(0, ("worker",))]
    if order:
        rules.append((order[0], ("fsdp",)))
        for i in order[1:]:
            rules.append((i, ("model",)))
    return _assign(shape, rules, mesh, allow_uneven=allow_uneven)


def leaf_serve_spec(shape, mesh: Mesh, allow_uneven: bool = False,
                    leaf_key: str = "") -> P:
    """Serving spec for one parameter leaf: largest dim tensor-parallel over
    'model', everything else replicated (params are replicated over 'data')."""
    order = _candidate_dims(shape, 0, leaf_key)
    rules = [(i, ("model",)) for i in order]
    return _assign(shape, rules, mesh, allow_uneven=allow_uneven)


def tree_specs(tree, leaf_rule, mesh: Mesh, **kw):
    """Map a per-leaf rule over a pytree of arrays / ShapeDtypeStructs."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: leaf_rule(a.shape, mesh, leaf_key=_leaf_key(path),
                                  **kw), tree)


def tree_shardings(tree_or_specs, mesh: Mesh):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_or_specs,
                        is_leaf=lambda x: isinstance(x, P))


def pad_to_multiple(n: int, m: int) -> int:
    return int(math.ceil(n / max(m, 1)) * max(m, 1))
