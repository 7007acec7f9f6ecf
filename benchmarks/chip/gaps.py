"""The numbers that decide ``correct`` for a training cell.

Norms are taken per leaf, and a stacked leaf (a leading layer axis, its
path under ``layers/``) counts as one leaf per layer.  A gap between the
program's norm and the reference's is measured against the larger of the
reference's norm of that leaf and the median leaf's, since some
gradients are all but zero; a cell's number is the worst leaf's gap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import seedweights
from seedweights import path_name

# a leaf whose reference gradient is under this share of the median
# leaf's moves under Adam by round-off alone (a key's bias under
# softmax); it is left out of the comparison of the parameters' change
NOUGHT_SHARE = 1e-3


def _norms(x, stacked: bool):
    x = x.astype(jnp.float32)
    if stacked:
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))[None]


def _stacked(name: str) -> bool:
    return name.startswith("layers/")


@jax.jit
def leaf_norms(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_name(p): _norms(x, _stacked(path_name(p))) for p, x in flat}


@jax.jit
def change_norms(params, key):
    """Leaf norms of ``params`` less the seed's initial weights, which
    are drawn again leaf by leaf inside this program rather than kept
    (a second copy of the weights would count in the device's peak)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = {}
    for p, a in flat:
        name = path_name(p)
        init = seedweights.leaf_value(key, name, a.shape, a.dtype)
        out[name] = _norms(a.astype(jnp.float32) - init.astype(jnp.float32),
                           _stacked(name))
    return out


def to_host(norms: dict) -> dict:
    return {k: np.asarray(v, np.float64) for k, v in
            jax.device_get(norms).items()}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref leaf), over
    the leaves ``keep`` marks (all where it is None)."""
    names = sorted(ref)
    r = np.concatenate([ref[n] for n in names])
    p = np.concatenate([prog[n] for n in names])
    k = (np.ones_like(r, bool) if keep is None
         else np.concatenate([keep[n] for n in names]))
    med = float(np.median(r[k])) if k.any() else 0.0
    gap = np.abs(p - r) / np.maximum(np.maximum(r, med), 1e-30)
    return float(np.max(gap[k])) if k.any() else 0.0


def moved_by_gradient(ref_grad: dict) -> dict:
    """The leaves whose reference gradient is not nought to rounding."""
    med = float(np.median(np.concatenate(list(ref_grad.values()))))
    return {n: v >= NOUGHT_SHARE * med for n, v in ref_grad.items()}


def loss_gap(prog_losses, ref_losses) -> float:
    return float(max(abs(p - r) / abs(r)
                     for p, r in zip(prog_losses, ref_losses)))
