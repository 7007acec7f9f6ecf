"""SPMD step builders: train_step / prefill_step / decode_step wired to
the mesh with the sharding rules (the Piper strategy lowered to pjit —
DESIGN.md §2, 'logical streams -> XLA scheduling lanes')."""
from __future__ import annotations


import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import numpy as np

from ..models import ArchConfig, decode_step, prefill, train_loss
from ..optim import adamw_update
from ..parallel.sharding import (ShardingRules, batch_shardings,
                                 cache_shardings, opt_state_shardings,
                                 params_shardings)
from .specs import batch_specs, cache_specs, params_specs, state_specs


def _logits_sharding(mesh: Mesh, strat: ShardingRules, batch: int):
    ax = strat.dp_axes if len(strat.dp_axes) > 1 else strat.dp_axes[0]
    size = int(np.prod([mesh.shape[a] for a in
                        (ax if isinstance(ax, tuple) else (ax,))]))
    if batch % size:
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(ax, None, None))


def strategy_for(mesh: Mesh, zero_stage: int = 3, core=None,
                 **kw) -> ShardingRules:
    """The pjit step builders' sharding rules, derived from ONE source
    of truth: a first-class ``core.strategy.Strategy``.  Pass ``core=``
    to drive the lowering from a declarative strategy document (the
    same JSON ``--strategy`` replays through the Piper-IR backends);
    the legacy ``zero_stage=`` spelling builds the equivalent ZeRO
    fragment and routes through the same derivation.  ``kw`` overrides
    pass through (``attn_mode``, ``seq_axis``, ``moe_impl``, ...)."""
    if core is None:
        from ..core.strategy import Strategy as CoreStrategy
        from ..core.strategy import ZeRO
        core = CoreStrategy(None, (ZeRO(stage=zero_stage),))
    elif core.zero is None:
        # a doc WITH a ZeRO fragment overrides the CLI; a doc without
        # one leaves the caller's zero_stage in force (the pre-unified
        # behavior dryrun's --zero help documents)
        kw.setdefault("zero_stage", zero_stage)
    return ShardingRules.from_core(core, mesh, **kw)


def make_train_fn(cfg: ArchConfig, lr: float = 3e-4):
    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: train_loss(cfg, p, batch))(state["params"])
        new_params, new_opt, gnorm = adamw_update(
            state["params"], grads, state["opt"], lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        return new_state, {"loss": loss, "gnorm": gnorm}
    return step


def make_prefill_fn(cfg: ArchConfig, max_seq: int):
    def step(params, batch):
        return prefill(cfg, params, batch, max_seq)
    return step


def make_decode_fn(cfg: ArchConfig):
    def step(params, cache, batch):
        logits, new_cache = decode_step(cfg, params, batch["token"], cache)
        return logits, new_cache
    return step


def jit_train_step(cfg: ArchConfig, mesh: Mesh, strat: ShardingRules,
                   shape_name: str = "train_4k"):
    """Returns (jitted_fn, (state_avals, batch_avals))."""
    state_avals = state_specs(cfg)
    batch_avals = batch_specs(cfg, shape_name)
    p_sh = params_shardings(state_avals["params"], mesh, strat)
    o_sh = {"m": opt_state_shardings(state_avals["opt"]["m"], mesh, strat),
            "v": opt_state_shardings(state_avals["opt"]["v"], mesh, strat),
            "step": NamedSharding(mesh, P())}
    state_sh = {"params": p_sh, "opt": o_sh,
                "step": NamedSharding(mesh, P())}
    b_sh = batch_shardings(batch_avals, mesh, strat)
    metric_sh = {"loss": NamedSharding(mesh, P()),
                 "gnorm": NamedSharding(mesh, P())}
    fn = jax.jit(make_train_fn(cfg),
                 in_shardings=(state_sh, b_sh),
                 out_shardings=(state_sh, metric_sh),
                 donate_argnums=(0,))
    return fn, (state_avals, batch_avals)


def jit_prefill_step(cfg: ArchConfig, mesh: Mesh, strat: ShardingRules,
                     shape_name: str = "prefill_32k"):
    from .specs import SHAPES
    seq = SHAPES[shape_name]["seq"]
    p_avals = params_specs(cfg)
    batch_avals = batch_specs(cfg, shape_name)
    cache_avals = jax.eval_shape(
        lambda p, b: prefill(cfg, p, b, seq)[1], p_avals, batch_avals)
    p_sh = params_shardings(p_avals, mesh, strat)
    b_sh = batch_shardings(batch_avals, mesh, strat)
    c_sh = cache_shardings(cache_avals, mesh, strat)
    logits_sh = _logits_sharding(mesh, strat,
                                 batch_avals["tokens"].shape[0])
    fn = jax.jit(make_prefill_fn(cfg, seq),
                 in_shardings=(p_sh, b_sh),
                 out_shardings=(logits_sh, c_sh))
    return fn, (p_avals, batch_avals)


def jit_decode_step(cfg: ArchConfig, mesh: Mesh, strat: ShardingRules,
                    shape_name: str = "decode_32k"):
    p_avals = params_specs(cfg)
    cache_avals = cache_specs(cfg, shape_name)
    batch_avals = batch_specs(cfg, shape_name)
    p_sh = params_shardings(p_avals, mesh, strat)
    c_sh = cache_shardings(cache_avals, mesh, strat)
    b_sh = batch_shardings(batch_avals, mesh, strat)
    logits_sh = _logits_sharding(mesh, strat,
                                 batch_avals["token"].shape[0])
    fn = jax.jit(make_decode_fn(cfg),
                 in_shardings=(p_sh, c_sh, b_sh),
                 out_shardings=(logits_sh, c_sh),
                 donate_argnums=(1,))
    return fn, (p_avals, cache_avals, batch_avals)


def axis_map_for(strat: ShardingRules) -> dict:
    dp = strat.dp_axes if len(strat.dp_axes) > 1 else strat.dp_axes[0]
    dpt = tuple(strat.dp_axes) + (strat.tp_axis,)
    return {"dp": dp, "tp": strat.tp_axis, "sp": strat.seq_axis,
            "dpt": dpt, "attn_tp": strat.attn_mode == "tp",
            "moe_a2a": strat.moe_impl == "a2a"}


def lower_cell(cfg: ArchConfig, mesh: Mesh, strat: ShardingRules,
               shape_name: str):
    """Lower (not compile) the right step for this cell."""
    from ..models import layers as L
    kind = {"train_4k": "train", "prefill_32k": "prefill",
            "decode_32k": "decode", "long_500k": "decode"}[shape_name]
    amap = axis_map_for(strat)
    amap["mesh"] = mesh
    L.set_axis_map(amap)
    try:
        with jax.set_mesh(mesh):
            if kind == "train":
                fn, avals = jit_train_step(cfg, mesh, strat, shape_name)
            elif kind == "prefill":
                fn, avals = jit_prefill_step(cfg, mesh, strat, shape_name)
            else:
                fn, avals = jit_decode_step(cfg, mesh, strat, shape_name)
            return fn.lower(*avals)
    finally:
        L.set_axis_map(None)
