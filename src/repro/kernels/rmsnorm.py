"""Fused RMSNorm Pallas kernel (TPU target, validated interpret=True).

Memory-bound op: fusing the mean-square reduction, rsqrt and scale into
one VMEM pass saves two HBM round-trips vs the unfused lowering.
Rows are tiled (BLOCK_ROWS, D) into VMEM; D stays whole (lane dim,
multiples of 128 for the VPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 128


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)).astype(o_ref.dtype) \
        * w_ref[...]


def rmsnorm_pallas(x: jax.Array, w: jax.Array, eps: float = 1e-6,
                   block_rows: int = BLOCK_ROWS,
                   interpret: bool = True) -> jax.Array:
    """x: (..., D), w: (D,).  Up to ``block_rows`` rows form one whole
    block; more are tiled ``block_rows`` (a multiple of 8) at a time,
    zero-padding the last block (a zero row normalizes to zero)."""
    orig_shape = x.shape
    d = x.shape[-1]
    n = x.size // d
    x2 = x.reshape(n, d)
    br = min(block_rows, n)
    n_pad = -(-n // br) * br
    if n_pad != n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n_pad // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), x.dtype),
        interpret=interpret,
    )(x2, w)
    return out[:n].reshape(orig_shape)
