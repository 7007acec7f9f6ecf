"""Selective-scan (Mamba1) Pallas kernel.

The recurrence h_t = exp(dt_t*A) * h_{t-1} + dt_t*B_t*x_t is independent
per channel, so the grid tiles (batch, channel-blocks, time-chunks); the
time axis is innermost and sequential, and the (N, BLOCK_C) state lives
in VMEM scratch across its chunks.  Inside a chunk a fori_loop walks
aligned slabs of ``SLAB`` time steps.  The decay terms are built
per-step in registers — the (S, C, N) tensor the naive lowering
materializes never exists.

Layout: inputs stay (B, S, C), so a time step is a row — the sublane
axis — and channels are the lanes; the state is held transposed,
(N, C), so each step updates it with row broadcasts.  Indexing time on
the lane axis instead cannot be proven aligned by Mosaic.  VMEM use is
bounded by the chunk, not the sequence.

TPU adaptation note (DESIGN.md §6): CUDA Mamba kernels parallelize the
scan across warps with shuffles; the TPU-native structure is
channel-block parallelism over the grid with a sequential VMEM-resident
inner loop (the VPU pipelines the elementwise recurrence), plus the
chunked formulation at the JAX level for sequence-level parallelism.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_C = 128
BLOCK_T = 512
# time steps per aligned load: one (16, 128) bf16 tile, two f32 tiles
SLAB = 16


def _scan_kernel(x_ref, dt_ref, at_ref, b_ref, c_ref, h0_ref, y_ref,
                 ht_ref, h_scr, *, n_slabs: int):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(f32)

    at = at_ref[...].astype(f32)                          # (N, bc)
    n = at.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    rows = jax.lax.broadcasted_iota(jnp.int32, (SLAB, at.shape[1]), 0)

    def column(row):
        # (1, N) row -> (N, 1) column without a transpose
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    def slab(k, h):
        off = pl.multiple_of(k * SLAB, SLAB)
        x = x_ref[0, pl.ds(off, SLAB), :].astype(f32)     # (SLAB, bc)
        dt = dt_ref[0, pl.ds(off, SLAB), :].astype(f32)
        bm = b_ref[0, pl.ds(off, SLAB), :].astype(f32)    # (SLAB, N)
        cm = c_ref[0, pl.ds(off, SLAB), :].astype(f32)
        y = jnp.zeros(x.shape, f32)
        for i in range(SLAB):
            dt_i = dt[i:i + 1]
            h = (h * jnp.exp(dt_i * at)
                 + column(bm[i:i + 1]) * (dt_i * x[i:i + 1]))
            y_i = jnp.sum(h * column(cm[i:i + 1]), axis=0, keepdims=True)
            y = jnp.where(rows == i, y_i, y)
        y_ref[0, pl.ds(off, SLAB), :] = y.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, n_slabs, slab, h_scr[...])
    h_scr[...] = h
    ht_ref[0] = h.astype(ht_ref.dtype)


def mamba_scan_pallas(xz, dt, A, B, C, D, h0=None,
                      block_c: int = BLOCK_C, block_t: int = BLOCK_T,
                      interpret: bool = True):
    """Same contract as models.layers.ssm_scan_ref:
    xz/dt: (B,S,C); A: (C,N); B,C: (B,S,N); D: (C,).
    Returns (y (B,S,C), hT (B,C,N)).

    Up to ``block_c`` channels are one whole block; more are tiled by
    ``block_c`` (a multiple of 128).  Time is cut into chunks of up to
    ``block_t`` steps (a multiple of ``SLAB``).  Both are zero-padded at
    the end: a padded step has dt = 0, so it leaves the state as it
    was."""
    b, s, c = xz.shape
    n = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((b, c, n), jnp.float32)
    bc = min(block_c, c)
    c_pad = -(-c // bc) * bc
    bt = min(block_t, -(-s // SLAB) * SLAB)
    s_pad = -(-s // bt) * bt
    pad_sc = ((0, 0), (0, s_pad - s), (0, c_pad - c))
    x_p = jnp.pad(xz, pad_sc)
    dt_p = jnp.pad(dt, pad_sc)
    b_p = jnp.pad(B, ((0, 0), (0, s_pad - s), (0, 0)))
    c_p = jnp.pad(C, ((0, 0), (0, s_pad - s), (0, 0)))
    at = jnp.pad(A, ((0, c_pad - c), (0, 0))).T             # (N, C)
    h0t = jnp.pad(h0, ((0, 0), (0, c_pad - c), (0, 0))).swapaxes(1, 2)

    y, ht = pl.pallas_call(
        functools.partial(_scan_kernel, n_slabs=bt // SLAB),
        grid=(b, c_pad // bc, s_pad // bt),
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bt, bc), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((n, bc), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bt, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, bt, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, n, bc), lambda i, j, k: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bc), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, n, bc), lambda i, j, k: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_pad, c_pad), xz.dtype),
            jax.ShapeDtypeStruct((b, n, c_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_p, dt_p, at, b_p, c_p, h0t)
    y = y[:, :s, :c] + xz * D.astype(xz.dtype)
    return y, ht[:, :, :c].swapaxes(1, 2)
