"""Grouped (per-expert) matmul Pallas kernel — the MoE compute hot-spot.

y[e] = x[e] @ w[e] for e in experts, tiled (BLOCK_M rows x BLOCK_N cols)
per grid step with the full contraction dim in VMEM (d_model up to 8k:
a 128 x 8192 bf16 tile is 2 MiB — comfortably inside the ~16 MiB VMEM
budget, and MXU-aligned).  Grid: (E, cap/BLOCK_M, f/BLOCK_N).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_M = 128
BLOCK_N = 128


def _gmm_kernel(x_ref, w_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)          # (bm, d)
    w = w_ref[0].astype(jnp.float32)          # (d, bn)
    o_ref[0] = (x @ w).astype(o_ref.dtype)


def moe_gmm_pallas(x: jax.Array, w: jax.Array,
                   block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                   interpret: bool = True) -> jax.Array:
    """x: (E, cap, d), w: (E, d, f) -> (E, cap, f).  A dim no longer
    than its block is one whole block; a longer one is tiled by the
    block (``block_m`` a multiple of 8, ``block_n`` of 128) and
    zero-padded to a multiple of it."""
    e, cap, d = x.shape
    f = w.shape[-1]
    bm = min(block_m, cap)
    bn = min(block_n, f)
    cap_pad = -(-cap // bm) * bm
    f_pad = -(-f // bn) * bn
    if cap_pad != cap:
        x = jnp.pad(x, ((0, 0), (0, cap_pad - cap), (0, 0)))
    if f_pad != f:
        w = jnp.pad(w, ((0, 0), (0, 0), (0, f_pad - f)))
    out = pl.pallas_call(
        _gmm_kernel,
        grid=(e, cap_pad // bm, f_pad // bn),
        in_specs=[
            pl.BlockSpec((1, bm, d), lambda ei, i, j: (ei, i, 0)),
            pl.BlockSpec((1, d, bn), lambda ei, i, j: (ei, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ei, i, j: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cap_pad, f_pad), x.dtype),
        interpret=interpret,
    )(x, w)
    return out[:, :cap, :f]
