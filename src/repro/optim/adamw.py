"""AdamW with fp32 moments (m, v), decoupled weight decay and global-norm
clipping.  Pure functions over pytrees so pjit shards the moments with
the ZeRO rules in parallel/sharding.py.  ``adamw_update`` runs under
``jax.named_scope("adamw")``, so a device trace finds its ops."""
from __future__ import annotations


import jax
import jax.numpy as jnp


def adamw_init(params) -> dict:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree_util.tree_map(zeros32, params),
            "v": jax.tree_util.tree_map(zeros32, params),
            "step": jnp.zeros((), jnp.int32)}


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree_util.tree_leaves(tree)))


@jax.named_scope("adamw")
def adamw_update(params, grads, opt, lr, *, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay=0.1, clip_norm=1.0):
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
    step = opt["step"] + 1
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / bc1
        vh = v / bc2
        new_p = p.astype(jnp.float32) - lr * (
            mh / (jnp.sqrt(vh) + eps) + weight_decay * p.astype(jnp.float32))
        return new_p.astype(p.dtype), m, v

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(opt["m"])
    flat_v = jax.tree_util.tree_leaves(opt["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    return new_params, {"m": new_m, "v": new_v, "step": step}, gnorm
