"""The grouped matmul of a training step's expert layer: rows sorted by
expert, each group of rows times its expert's matrix, for the experts
this device holds, with a backward.

On one TPU it is JAX's megablox kernels
(``jax.experimental.pallas.ops.tpu.megablox``): ``gmm`` forward and for
the rows' gradient, ``tgmm`` for the matrices', under megablox's custom
VJP; ``group_offset`` makes them compute the held experts' groups alone,
and their output rows of other experts are zero.  Elsewhere
``jax.lax.ragged_dot`` computes the same, the other experts' rows
against zero matrices.

``models.layers`` decides which runs (``gmm_fused``).  This module
imports nothing of the model, so the model can import it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

LANES = 128
# a tile set's scoped VMEM, as the kernels take it: both inputs' tiles
# double-buffered, the float32 accumulator and the output tile
# double-buffered, in bytes; the compiler's scoped limit is 16 MiB
VMEM_BUDGET = 12 * 2**20


def _divisors(n: int, step: int, cap: int) -> list:
    return [t for t in range(step, min(n, cap) + 1, step) if n % t == 0]


def tiling(m: int, k: int, n: int):
    """The kernels' (rows, contraction, columns) tiles for ``m`` rows of
    ``k`` times ``k`` x ``n`` matrices: each divides its dimension (rows
    a multiple of 8 up to 512; the others multiples of 128), and of the
    sets within ``VMEM_BUDGET`` the one with the most rows, then the
    largest contraction x columns tile.  None where no set fits.  The
    megablox kernels call it again with their own dimensions for the
    backward (``gmm`` of the rows' gradient, ``tgmm``)."""
    best = None
    for tm in _divisors(m, 8, 512):
        for tk in _divisors(k, LANES, k):
            for tn in _divisors(n, LANES, n):
                vmem = 4 * tm * (tk + tn) + 8 * tk * tn
                if vmem <= VMEM_BUDGET and (best is None or (tm, tk * tn)
                                            > (best[0], best[1] * best[2])):
                    best = (tm, tk, tn)
    return best


def grouped_matmul(x, w, group_sizes, group_offset: int, *, fused: bool):
    """x (M, K) rows sorted by expert, ``group_sizes`` (E,) int32 rows of
    each of the E experts; w (G, K, N) the matrices of experts
    ``group_offset`` to ``group_offset + G - 1``.  Returns (M, N): each
    held expert's rows times its matrix, every other row zero, in x's
    dtype with float32 accumulation.  ``fused`` runs the megablox
    kernels (one TPU, ``tiling`` not None for either matmul of the
    backward too)."""
    m, k = x.shape
    g, _, n = w.shape
    if fused:
        return megablox.gmm(x, w, group_sizes, x.dtype, tiling,
                            jnp.asarray(group_offset, jnp.int32))
    before = jnp.sum(group_sizes[:group_offset])
    held = group_sizes[group_offset:group_offset + g]
    sizes = jnp.concatenate([before[None], held,
                             (m - before - jnp.sum(held))[None]])
    none = jnp.zeros((1, k, n), w.dtype)
    return jax.lax.ragged_dot(x, jnp.concatenate([none, w, none]),
                              sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
