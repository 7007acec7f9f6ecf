"""Lane ``train_pjit``: the trainer's own loop, ``repro.ft.Supervisor.run``
over ``repro.launch.train.build_step``, fed by ``TokenLoader`` over
``SyntheticTokenSource`` seeded by ``--seed``.

One step object and one state are built and driven through
``Supervisor.run`` from the seed: steps 1 to 3 are the ones the
reference follows, and the measured window takes the same object on
from step ``WARM_STEPS + 1``.  The harness's step wrapper stamps each
dispatch, so the loader and the loop's own per-step work fall inside
the window; the restart snapshot ``Supervisor.run`` takes first falls
in set-up.  No checkpoint is saved.  When the window has closed, the
wrapper stops the loop by raising ``WindowClosed``.

Read for ``correct``: the loss of steps 1 to 3, the norm of the first
gradient as AdamW gets it (its first moment after step 1 over 1 - b1),
and the norm of the parameters' change over steps 1 to 3, as step 4
receives them, each leaf against the plain reference (``gaps.py``): the
module ``reference/<name>.py`` that the configuration's ``reference``
names.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import shutil
import tempfile
import time

import numpy as np

import gaps
import seedweights
from reference import tokens as ref_tokens

# steps before the window: 1 compiles, 1-3 are compared, 4 reads the
# change, 5 runs clean
WARM_STEPS = 5
COMPARED_STEPS = 3


class WindowClosed(Exception):
    pass


def reference_of(c: dict):
    """The plain reference the configuration names."""
    return importlib.import_module(f"reference.{c['reference']}")


def program_config(c: dict):
    """The program's ArchConfig for the configuration file, checked
    against the file's published keys: ``program_keys`` maps each field
    of the program's config to the published key it must equal."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(c["arch"]), **c["program"])
    want = {k: c[published] for k, published in c["program_keys"].items()}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"program config {got} is not the file's {want}")
    return cfg


def program_step(cfg, c: dict):
    from repro.launch.train import build_step
    from repro.optim import cosine_schedule, wsd_schedule
    opt = c["optimizer"]
    sched = {"cosine": cosine_schedule, "wsd": wsd_schedule}[opt["schedule"]]
    return build_step(cfg, sched(opt["peak_lr"], opt["horizon_steps"]))


class TimedLoader:
    """The program's loader inside the benchmark's ``loader`` span."""

    def __init__(self, loader, spans) -> None:
        self.loader = loader
        self.spans = spans

    def next_batch(self):
        self.spans.close("block")
        with self.spans.span("loader"):
            return self.loader.next_batch()

    def state_dict(self):
        return self.loader.state_dict()

    def load_state_dict(self, d):
        self.loader.load_state_dict(d)


class Stepper:
    """The step wrapper ``Supervisor.run`` calls: reads the compared
    numbers before steps 2 and 4, stamps every dispatch, and closes the
    window."""

    def __init__(self, ctx, step_fn, b1: float) -> None:
        self.ctx = ctx
        self.step_fn = step_fn
        self.seed = ctx.seed
        self.b1 = b1
        self.k = 0                    # steps dispatched so far
        self.stamps: list[float] = []  # dispatch times of window steps
        self.grad_norms = None
        self.change_norms = None
        self.state = None

    def __call__(self, state, batch):
        import jax
        ctx = self.ctx
        k = self.k + 1
        if k <= WARM_STEPS + 1:
            ctx.mark(f"step{k}")
        if k == 2:
            m = gaps.to_host(gaps.leaf_norms(state["opt"]["m"]))
            self.grad_norms = {n: v / (1 - self.b1) for n, v in m.items()}
        elif k == COMPARED_STEPS + 1:
            self.change_norms = gaps.to_host(gaps.change_norms(
                state["params"], seedweights.seed_key(self.seed)))
        if k == WARM_STEPS + 1:
            jax.block_until_ready(state)
            ctx.window_opens()
        now = time.perf_counter()
        if k > WARM_STEPS:
            self.stamps.append(now)
            if now - self.stamps[0] >= ctx.seconds:
                self.state = state
                raise WindowClosed
        self.k = k
        with ctx.spans.span("dispatch"):
            out = self.step_fn(state, batch)
        ctx.spans.open("block")
        return out


def run(ctx) -> dict:
    import jax
    from repro.checkpoint import CheckpointManager
    from repro.data import SyntheticTokenSource, TokenLoader
    from repro.ft import Supervisor
    from repro.models import init
    from repro.optim import adamw_init

    cell, seed = ctx.cell, ctx.seed
    c = cell.config
    batch, seq = cell.traffic["batch"], cell.traffic["seq"]
    cfg = program_config(c)
    avals = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    if (seedweights.names_and_shapes(avals)
            != seedweights.names_and_shapes(reference_of(c).param_avals(c))):
        raise ValueError("the program's parameter tree is not the "
                         "reference's")
    step_fn = (ctx.make_step(cfg, c) if ctx.make_step
               else program_step(cfg, c))
    make_params = seedweights.maker(avals)
    with ctx.spans.span("setup.params"):
        params = make_params(seed)
        state = {"params": params, "opt": jax.jit(adamw_init)(params),
                 "step": jax.numpy.zeros((), jax.numpy.int32)}
        jax.block_until_ready(state)
    ctx.mark("params")
    loader = TimedLoader(TokenLoader(SyntheticTokenSource(cfg.vocab,
                                                          seed=seed),
                                     batch=batch, seq=seq), ctx.spans)
    ckpt_dir = tempfile.mkdtemp(prefix="chipbench_ckpt_")
    stepper = Stepper(ctx, step_fn, c["optimizer"]["b1"])
    sup = Supervisor(CheckpointManager(ckpt_dir, keep=1, async_save=False),
                     loader, checkpoint_every=1 << 62)
    try:
        sup.run(state, stepper, n_steps=1 << 62, log_every=0)
        raise RuntimeError("the training loop ended before the window")
    except WindowClosed:
        pass
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    ctx.spans.close("block")
    state = stepper.state
    del params
    jax.block_until_ready(state)
    ctx.window_closes()
    stamps = stepper.stamps
    window_s = stamps[-1] - stamps[0]
    n_steps = len(stamps) - 1
    history = sup.history
    window_losses = [h["loss"] for h in history[WARM_STEPS:]]
    failed = sum(not math.isfinite(x) for x in window_losses)
    prog_losses = [h["loss"] for h in history[:COMPARED_STEPS]]
    # the program's state goes before the reference runs
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    del state, stepper.state
    with ctx.spans.span("reference"):
        ref = reference_readings(c, seed, batch, seq)
    keep = gaps.moved_by_gradient(ref["grad_norms"])
    lim = cell.limits
    checks = [
        ("loss_gap", gaps.loss_gap(prog_losses, ref["losses"]),
         lim["loss_gap"]),
        ("grad_norm_gap", gaps.worst_leaf_gap(stepper.grad_norms,
                                              ref["grad_norms"]),
         lim["grad_norm_gap"]),
        ("change_norm_gap", gaps.worst_leaf_gap(stepper.change_norms,
                                                ref["change_norms"], keep),
         lim["change_norm_gap"]),
    ]
    return {"window_s": window_s, "steps": n_steps,
            "tokens": n_steps * batch * seq,
            "intervals": list(np.diff(stamps)),
            "window": (stamps[0], stamps[-1]),
            "attempted": n_steps, "failed": failed, "checks": checks,
            "lane_info": {},
            "readings": {"prog_losses": prog_losses,
                         "ref_losses": ref["losses"]}}


def reference_readings(c: dict, seed: int, batch: int, seq: int,
                       precision: str = "f32") -> dict:
    """The plain reference's three steps from the seed: each step's loss,
    the first clipped gradient's leaf norms, and the leaf norms of the
    parameters' change over the three steps."""
    import jax
    ref = reference_of(c)
    make_params = seedweights.maker(ref.param_avals(c))
    step = ref.make_step(c, precision)
    state = ref.init_state(make_params(seed))
    losses, grad_norms = [], None
    b1 = c["optimizer"]["b1"]
    for i in range(COMPARED_STEPS):
        b = ref_tokens.batch_at(c["vocab_size"], seed, i, batch, seq)
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
        if i == 0:
            m = gaps.to_host(gaps.leaf_norms(state["opt"]["m"]))
            grad_norms = {n: v / (1 - b1) for n, v in m.items()}
    change = gaps.to_host(gaps.change_norms(state["params"],
                                            seedweights.seed_key(seed)))
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def compile_for(cell, device):
    """The cell's step compiled for ``device`` (a described chip) from
    shapes alone, as ``compile_check.py`` reads it."""
    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.models import init
    from repro.optim import adamw_init
    c = cell.config
    cfg = program_config(c)
    one = SingleDeviceSharding(device)

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)

    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(place, {
        "params": params, "opt": jax.eval_shape(adamw_init, params),
        "step": jax.ShapeDtypeStruct((), jax.numpy.int32)})
    b, s = cell.traffic["batch"], cell.traffic["seq"]
    batch = {k: place(jax.ShapeDtypeStruct((b, s), jax.numpy.int32))
             for k in ("tokens", "labels")}
    return program_step(cfg, c).lower(state, batch).compile()


FAULTS = ("half_batch", "unchanged", "double_update")


def half(batch: dict) -> dict:
    """Half of the batch: half of its rows, or the first half of each row
    where the batch is one row."""
    rows = next(iter(batch.values())).shape[0]
    if rows > 1:
        return {k: v[:rows // 2] for k, v in batch.items()}
    return {k: v[:, :v.shape[1] // 2] for k, v in batch.items()}


def fault_step(mode: str):
    """A ``make_step(cfg, config)`` that puts the control or a fault in
    the program's place, or None for ``program``:

      fp8            the plain reference, its matmuls in float8
      half_batch     the program's step on half of each batch, the mean
                     taken over the rest
      unchanged      a step that returns its state unchanged
      double_update  the step's update of one leaf (the layers' query
                     matrices) applied twice, where the step produces it
    """
    import jax
    if mode == "program":
        return None
    if mode == "fp8":
        return lambda cfg, c: reference_of(c).make_step(c, "fp8")
    if mode not in FAULTS:
        raise ValueError(f"unknown mode {mode!r}")

    def make(cfg, c):
        step = program_step(cfg, c)
        if mode == "half_batch":
            return lambda state, batch: step(state, half(batch))
        if mode == "unchanged":
            from repro.models import train_loss

            @jax.jit
            def same(state, batch):
                return state, {"loss": train_loss(cfg, state["params"],
                                                  batch)}
            return same

        @jax.jit
        def double(state, batch):
            old = state["params"]["layers"]["attn"]["wq"]
            new_state, metrics = step(state, batch)
            new = new_state["params"]["layers"]["attn"]["wq"]
            moved = old + 2 * (new.astype("float32") - old.astype("float32"))
            new_state["params"]["layers"]["attn"]["wq"] = moved.astype(
                new.dtype)
            return new_state, metrics
        return double
    return make
