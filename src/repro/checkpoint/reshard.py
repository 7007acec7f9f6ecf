"""ZeRO shard remapping across data-parallel degree changes.

Under ZeRO-2/3 every DP rank owns a 1/d flat slice of each gradient /
parameter leaf.  When the elastic planner shrinks (or regrows) the DP
degree, the surviving ranks must *regather* the old shards and re-slice
them for the new degree — this module is that codec, and it is required
to be **bit-exact**: resharding is a placement change, never a numerics
change (tests/test_property.py round-trips it under hypothesis).

Shard layout (the repo-wide convention, matching ``Replicate``'s
flat-bucket sharding): a leaf is flattened C-order, zero-padded up to a
multiple of the degree, and split into ``degree`` equal contiguous
slices — rank ``i`` owns slice ``i``.  The pad bytes are never part of
the restored value (``unshard_leaf`` truncates to the true element
count), so padding cannot leak across a degree change.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


class ReshardError(ValueError):
    """A shard remap failed integrity verification."""


def _check_degree(degree: int) -> None:
    if not isinstance(degree, int) or isinstance(degree, bool) \
            or degree < 1:
        raise ReshardError(f"shard degree must be a positive int, "
                           f"got {degree!r}")


def shard_leaf(arr, degree: int) -> list[np.ndarray]:
    """Flatten ``arr`` and split it into ``degree`` equal contiguous
    shards (last ones zero-padded)."""
    _check_degree(degree)
    a = np.asarray(arr)
    flat = a.reshape(-1)
    chunk = -(-flat.size // degree) if flat.size else 0
    pad = chunk * degree - flat.size
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, a.dtype)])
    return [flat[i * chunk:(i + 1) * chunk].copy()
            for i in range(degree)]


def unshard_leaf(shards: Sequence[np.ndarray], shape, dtype) -> np.ndarray:
    """Reassemble a full leaf from its ordered shards (inverse of
    ``shard_leaf``; drops the pad)."""
    dtype = np.dtype(dtype)
    parts = [np.asarray(s).reshape(-1) for s in shards]
    flat = (np.concatenate(parts) if parts
            else np.zeros((0,), dtype))
    n = int(np.prod(shape, dtype=np.int64)) if len(shape) else 1
    return np.ascontiguousarray(flat[:n]).astype(dtype, copy=False) \
        .reshape(shape)


def remap_shards(shards: Sequence[np.ndarray], new_degree: int,
                 n_elements: int) -> list[np.ndarray]:
    """Regather + re-slice: old-degree shards -> new-degree shards.
    ``n_elements`` is the true (unpadded) leaf size — the old pad is
    stripped before re-padding for the new degree."""
    _check_degree(new_degree)
    parts = [np.asarray(s).reshape(-1) for s in shards]
    flat = np.concatenate(parts) if parts else np.zeros((0,))
    return shard_leaf(flat[:n_elements], new_degree)


def shard_tree(tree, degree: int) -> list:
    """Per-rank pytrees of flat shards: ``shard_tree(t, d)[i]`` is what
    DP rank ``i`` owns (same treedef as ``tree``)."""
    _check_degree(degree)
    return [jax.tree_util.tree_map(
        lambda x, i=i: shard_leaf(x, degree)[i], tree)
        for i in range(degree)]


def unshard_tree(per_rank: Sequence, tree_like):
    """Inverse of ``shard_tree``: reassemble the full tree, taking
    shapes/dtypes from ``tree_like``."""
    flat_like, treedef = jax.tree_util.tree_flatten(tree_like)
    rank_leaves = [jax.tree_util.tree_leaves(t) for t in per_rank]
    out = []
    for k, leaf in enumerate(flat_like):
        shards = [rl[k] for rl in rank_leaves]
        out.append(unshard_leaf(shards, np.shape(leaf),
                                np.asarray(leaf).dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def reshard_tree(tree, old_degree: int, new_degree: int, *,
                 verify: bool = True):
    """Remap every leaf of ``tree`` from ``old_degree`` ZeRO shards to
    ``new_degree`` and reassemble — the elastic restore path
    (``ft.elastic.ElasticSupervisor``) runs restored params/opt state
    through this whenever the shrunk mesh changes the DP width.

    With ``verify=True`` (default) every leaf's reassembled bytes are
    checked against the input — a reshard that is not bit-identical is
    corruption, not a rounding question — and ``ReshardError`` names the
    first differing leaf."""
    _check_degree(old_degree)
    _check_degree(new_degree)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        a = np.asarray(leaf)
        shards = remap_shards(shard_leaf(a, old_degree), new_degree,
                              a.size)
        full = unshard_leaf(shards, a.shape, a.dtype)
        if verify and full.tobytes() != a.tobytes():
            raise ReshardError(
                f"ZeRO reshard {old_degree}->{new_degree} corrupted "
                f"leaf {jax.tree_util.keystr(path)} "
                f"(shape {a.shape}, dtype {a.dtype})")
        # jnp would narrow 64-bit leaves while x64 is off: such a leaf
        # stays a numpy array so the reshard keeps its dtype
        out.append(jnp.asarray(full)
                   if jax.dtypes.canonicalize_dtype(full.dtype) == full.dtype
                   else full)
    return jax.tree_util.tree_unflatten(treedef, out)


__all__ = ["ReshardError", "remap_shards", "reshard_tree", "shard_leaf",
           "shard_tree", "unshard_leaf", "unshard_tree"]
