"""Plain reference of the IR lane's proxy program: stages
``h -> tanh(h @ w1) @ w2`` applied in order, the last stage's output
against ``y`` by mean squared error, in float32 at the ``highest``
matmul precision.  Imports nothing of the program; the stage names and
shapes come from the parameter tree it is given (``stage<i>`` leaves
``w1``, ``w2``, and an inert ``bank`` that no stage multiplies).

``precision="fp8"`` is the control: the matmuls take their operands
through float8 as in ``dense_lm``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.dense_lm import make_mm


def stage_names(params: dict) -> list[str]:
    return sorted(params, key=lambda s: int(s.removeprefix("stage")))


def loss_fn(mm, params, x, y):
    h = x.astype(jnp.float32)
    names = stage_names(params)
    for name in names:
        p = params[name]
        h = mm("td,dk->tk", jnp.tanh(mm("td,dk->tk", h, p["w1"])), p["w2"])
    return jnp.mean((h - y.astype(jnp.float32)) ** 2)


def make_loss_and_grads(precision: str = "f32"):
    mm = make_mm(precision)

    @jax.jit
    def run(params, batch):
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
        return jax.value_and_grad(
            lambda p: loss_fn(mm, p, batch["x"], batch["y"]))(p32)

    return run
