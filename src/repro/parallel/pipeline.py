"""SPMD pipeline parallelism over a ("pipe", …) mesh axis.

MPMD -> SPMD adaptation (DESIGN.md §2): every rank runs the SAME jitted
program; a ``lax.scan`` over M + R - 1 steps shifts stage-boundary
activations to the next rank with ``lax.ppermute`` each step, and a rank
is "active" when its microbatch index t - r lands in [0, M).  Autodiff
through the scan + ppermute yields the exact reverse pipeline, so one
forward definition gives training with GPipe semantics (all-forward /
all-backward, boundary activations stashed per microbatch).

Arbitrary static tables (1F1B / interleaved / DualPipeV) are executed by
the Piper runtime from per-device plans (core/schedules.py + the
interpreter) and modelled by the timeline simulator; this module is the
single-program lane that proves pipeline placement composes with the
production mesh's data/model axes (launch/dryrun has a --pp lane).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, x_microbatches,
                   *, mesh: Mesh, axis: str = "pipe"):
    """Run a pipeline of R = mesh.shape[axis] stages.

    stage_fn(stage_params, x) -> y          (same shape as x)
    params_stacked: pytree with leading dim R (stage-major), sharded so
      each pipe rank holds its stage (P(axis, ...)).
    x_microbatches: (M, mb, ...) inputs (replicated along the pipe axis).
    Returns (M, mb, ...) outputs of the LAST stage (valid on every rank;
    produced on rank R-1 and broadcast back via ppermute ring-shift).
    """
    R = mesh.shape[axis]
    M = x_microbatches.shape[0]
    steps = M + R - 1
    fwd_perm = [(i, (i + 1) % R) for i in range(R)]

    def per_rank(params, x_mb):
        # params: stage params with leading dim 1 (this rank's stage)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        r = jax.lax.axis_index(axis)
        mb_shape = x_mb.shape[1:]
        y_acc = jnp.zeros((M,) + mb_shape, x_mb.dtype)

        def step(carry, t):
            prev_out, y_acc = carry
            # receive boundary activation from the left neighbour
            recv = jax.lax.ppermute(prev_out, axis, fwd_perm)
            my_mb = t - r
            active = (my_mb >= 0) & (my_mb < M)
            x_first = x_mb[jnp.clip(my_mb, 0, M - 1)]
            x_in = jnp.where(r == 0, x_first, recv)
            out = stage_fn(params, x_in)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # last stage banks its result
            is_last = r == R - 1
            y_acc = jax.lax.cond(
                active & is_last,
                lambda acc: acc.at[jnp.clip(my_mb, 0, M - 1)].set(out),
                lambda acc: acc, y_acc)
            return (out, y_acc), None

        init = (jnp.zeros(mb_shape, x_mb.dtype), y_acc)
        (last_out, y_acc), _ = jax.lax.scan(
            step, init, jnp.arange(steps))
        # broadcast the last rank's outputs to all ranks (psum of the
        # one-hot contribution)
        contrib = jnp.where(r == R - 1, y_acc, jnp.zeros_like(y_acc))
        return jax.lax.psum(contrib, axis)

    f = jax.shard_map(
        per_rank, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(
            lambda a: P(*([axis] + [None] * (a.ndim - 1))),
            params_stacked), P()),
        out_specs=P(),
        check_vma=False,
    )
    return f(params_stacked, x_microbatches)


def pipeline_loss(stage_fn, loss_fn, params_stacked, x_mb, y_mb, *,
                  mesh, axis="pipe"):
    """Mean loss over microbatches through the pipeline (differentiable:
    jax.grad of this yields the reverse pipeline)."""
    out = pipeline_apply(stage_fn, params_stacked, x_mb,
                         mesh=mesh, axis=axis)
    return loss_fn(out, y_mb)
