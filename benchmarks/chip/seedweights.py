"""Weights made from the run's seed, on the device, in one jitted call.

Both the program and the plain reference take their weights from here,
each calling it with the seed: the reference never reads the program's
arrays.  A leaf's values depend only on the seed, the leaf's path in the
tree and its shape, so the two trees need only agree on the paths.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any seed up to 2**64 (seeds may pass 32
    bits)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], dtype=jnp.uint32),
        impl="threefry2x32")


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_rule(name: str, shape) -> tuple[str, float]:
    """(kind, scale) of a leaf by its name: norm weights 1 + N(0, .05),
    biases and the embedding N(0, .02), matrices N(0, fan_in^-1/2)."""
    last = name.rsplit("/", 1)[-1]
    if "norm" in last:
        return "one_plus", 0.05
    if last.startswith("b") or last == "embed" or len(shape) < 2:
        return "normal", 0.02
    return "normal", float(shape[-2]) ** -0.5


def leaf_value(key, name: str, shape, dtype):
    """The values of the leaf ``name``, traced inside a caller's jit."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    kind, scale = leaf_rule(name, shape)
    x = jax.random.normal(k, shape, jnp.float32) * scale
    if kind == "one_plus":
        x = 1.0 + x
    return x.astype(dtype)


def maker(avals):
    """A function ``seed -> tree`` shaped like ``avals``
    (ShapeDtypeStructs), each leaf drawn by ``leaf_rule`` in the leaf's
    own dtype by one jitted program; keep it to call it again without
    tracing anew."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(avals)
    names = [path_name(p) for p, _ in flat]
    specs = [(a.shape, a.dtype) for _, a in flat]

    @jax.jit
    def build(key):
        return [leaf_value(key, n, s, d) for n, (s, d) in zip(names, specs)]

    return lambda seed: jax.tree_util.tree_unflatten(
        treedef, build(seed_key(seed)))


def names_and_shapes(avals) -> list[tuple[str, tuple]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(avals)
    return [(path_name(p), tuple(np.shape(a))) for p, a in flat]
