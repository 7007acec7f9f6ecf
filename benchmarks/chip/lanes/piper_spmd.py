"""Lane ``piper_spmd``: the Piper-IR path across chips.  The cell's
``Strategy`` document compiles the configuration's proxy program
(``tune.build_strategy_program``: ``compile_training`` with its passes
and certifier), ``make_executor("spmd", ...)`` lowers it to one SPMD
program over the mesh, and the window drives ``SpmdExecutor.run`` with a
new batch each step from ``launch.train._ProgramLoader`` seeded by
``--seed``.

Each step is timed from the start of one loop iteration to the next:
the loader, ``run`` (dispatch, the loss sync and the host's gradient
assembly) and the wait for the assembled gradients.  Steps 1 to 3 run
before the window and are the ones compared: each step's loss and the
norm of every stage's gradient, as the plan produced them through the
pipeline's p2p, the ZeRO-3 gathers and the gradient reduce-scatter,
against the plain reference (``reference/proxy_chain.py``).
"""
from __future__ import annotations

import json
import math
import time

import numpy as np

import gaps
import seedweights
from reference import program_batches, proxy_chain

WARM_STEPS = 4
COMPARED_STEPS = 3


def matmul_params(params: dict) -> int:
    return sum(int(np.prod(p[k].shape)) for p in params.values()
               for k in ("w1", "w2"))


def build(ctx):
    """(executor, loader, program) for the cell, from the seed."""
    from repro import tune
    from repro.core.strategy import Strategy
    from repro.launch.train import _ProgramLoader
    from repro.runtime.executor import make_executor

    from lanes.train_pjit import program_config
    cell = ctx.cell
    cfg = program_config(cell.config)
    strat = Strategy.from_json(json.dumps(cell.workload["strategy"]))
    with ctx.spans.span("ir.compile"):
        prog, _ = tune.build_strategy_program(cfg, strat,
                                              cell.traffic["tokens"])
    ctx.mark("ir.compile")
    with ctx.spans.span("setup.params"):
        params = seedweights.maker(prog.params)(ctx.seed)
    ex = make_executor("spmd", prog, params=params)
    loader = _ProgramLoader(prog.input_shapes(), cfg.vocab, seed=ctx.seed)
    return ex, loader, prog


def run(ctx) -> dict:
    import jax
    ex, loader, prog = build(ctx)
    step = ctx.make_step(ex) if ctx.make_step else ex.run
    spans = ctx.spans
    losses, grad_norms, stamps = [], [], []
    window_losses = []
    k = 0
    while True:
        k += 1
        if k <= WARM_STEPS + 1:
            ctx.mark(f"step{k}")
        if k == WARM_STEPS + 1:
            ctx.window_opens()
        now = time.perf_counter()
        if k > WARM_STEPS:
            stamps.append(now)
            if now - stamps[0] >= ctx.seconds:
                break
        with spans.span("loader"):
            batch = loader.next_batch()
        with spans.span("ir.run"):
            res = step(batch)
        with spans.span("grad_assembly"):
            jax.block_until_ready(res.grads)
        if k <= COMPARED_STEPS:
            losses.append(res.loss)
            grad_norms.append(gaps.to_host(gaps.leaf_norms(res.grads)))
        elif k > WARM_STEPS:
            window_losses.append(res.loss)
    ctx.window_closes()
    # the program's weights go before the reference runs
    for leaf in jax.tree_util.tree_leaves(ex.params):
        leaf.delete()
    del ex, res
    with spans.span("reference"):
        ref = reference_readings(prog.params, prog.input_shapes(),
                                 ctx.seed)
    lim = ctx.cell.limits
    grad_gap = max(
        gaps.worst_leaf_gap(p, r, gaps.moved_by_gradient(r))
        for p, r in zip(grad_norms, ref["grad_norms"]))
    checks = [("loss_gap", gaps.loss_gap(losses, ref["losses"]),
               lim["loss_gap"]),
              ("grad_norm_gap", grad_gap, lim["grad_norm_gap"])]
    n = len(stamps) - 1
    return {"window_s": stamps[-1] - stamps[0], "steps": n,
            "tokens": n * ctx.cell.traffic["tokens"],
            "intervals": list(np.diff(stamps)),
            "window": (stamps[0], stamps[-1]),
            "attempted": n,
            "failed": sum(not math.isfinite(x) for x in window_losses),
            "checks": checks,
            "lane_info": {"matmul_params": matmul_params(prog.params)},
            "readings": {"prog_losses": losses,
                         "ref_losses": ref["losses"]}}


def reference_readings(avals, shapes: dict, seed: int,
                       precision: str = "f32") -> dict:
    """The plain reference's loss and gradient leaf norms for steps 1 to
    3, on the weights drawn again from the seed and on each step's batch
    made again from the seed (``avals`` gives only the tree's shapes)."""
    weights = seedweights.maker(avals)(seed)
    run = proxy_chain.make_loss_and_grads(precision)
    losses, norms = [], []
    for i in range(COMPARED_STEPS):
        loss, g = run(weights, program_batches.batch_at(shapes, seed, i))
        losses.append(float(loss))
        norms.append(gaps.to_host(gaps.leaf_norms(g)))
    return {"losses": losses, "grad_norms": norms}


FAULTS = ("half_batch", "no_exchange", "altered_grad")


class _Result:
    def __init__(self, loss, grads) -> None:
        self.loss = loss
        self.grads = grads


def fault_step(mode: str):
    """A ``make_step(executor)`` that puts the control or a fault in the
    program's place, or None for ``program``:

      fp8           the plain reference, its matmuls in float8, on the
                    executor's weights
      half_batch    the second half of each batch's rows replaced by the
                    first, so the mean is taken over half of the batch
      no_exchange   the pipeline's p2p exchange between chips left out
                    (each rank keeps what it would have sent)
      altered_grad  the first stage's gradient of w1 doubled where the
                    executor produces it
    """
    import jax
    import jax.numpy as jnp
    if mode == "program":
        return None
    if mode == "fp8":
        def control(ex):
            run = proxy_chain.make_loss_and_grads("fp8")

            def step(batch):
                loss, g = run(ex.params, batch)
                return _Result(float(loss), g)
            return step
        return control
    if mode not in FAULTS:
        raise ValueError(f"unknown mode {mode!r}")

    def make(ex):
        def step(batch):
            if mode == "half_batch":
                batch = {k: np.concatenate([v[:len(v) // 2]] * 2)
                         for k, v in batch.items()}
            if mode == "no_exchange":
                keep = jax.lax.ppermute
                jax.lax.ppermute = lambda x, axis_name, perm: x
                try:
                    res = ex.run(batch)
                finally:
                    jax.lax.ppermute = keep
                return res
            res = ex.run(batch)
            if mode == "altered_grad":
                first = proxy_chain.stage_names(res.grads)[0]
                res.grads[first]["w1"] = res.grads[first]["w1"] * jnp.asarray(
                    2, res.grads[first]["w1"].dtype)
            return res
        return step
    return make


def compile_for(cell, devices):
    """The cell's SPMD program compiled for ``devices`` (described chips)
    from shapes alone, as ``compile_check.py`` reads it.  The executor is
    built on as many host devices, then its mesh is pointed at the
    described ones before the program is traced."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import tune
    from repro.core.strategy import Strategy
    from repro.runtime import spmd
    from repro.runtime.executor import make_executor

    from lanes.train_pjit import program_config
    cfg = program_config(cell.config)
    strat = Strategy.from_json(json.dumps(cell.workload["strategy"]))
    prog, _ = tune.build_strategy_program(cfg, strat, cell.traffic["tokens"])
    ex = make_executor("spmd", prog, params=prog.params)
    ex.mesh = Mesh(np.array(devices), (spmd.AXIS,))
    batch = {k: np.zeros(s, np.dtype(d))
             for k, (s, d) in prog.input_shapes().items()}
    built = ex._build(batch)
    rep = NamedSharding(ex.mesh, P())
    per_rank = NamedSharding(ex.mesh, P(spmd.AXIS))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        prog.params)
    feeds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=per_rank)
             for k, v in ex._stack_feeds(batch).items()}
    return built.fn.lower(params, feeds).compile()
