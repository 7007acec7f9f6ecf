"""End-to-end training driver (deliverable b): trains a shipped config
at its published widths, or a reduced one when any of ``--d-model``,
``--layers`` or ``--vocab`` is given, on the default device, with the
full substrate — data pipeline, AdamW + schedule, checkpoint/restart via
the FT supervisor.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --steps 200 --d-model 256 --layers 4

A mid-run injected failure (--fail-at) demonstrates checkpoint-restart;
the run resumes from the last checkpoint with the exact data stream.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import math
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticTokenSource, TokenLoader
from repro.ft import FailureInjector, Supervisor
from repro.models import init, train_loss_and_stats
from repro.models.layers import attention_paths
from repro.optim import adamw_init, adamw_update, cosine_schedule, \
    wsd_schedule


def build_step(cfg, lr_fn):
    # the state is donated: params and both AdamW moments are updated in
    # place, so a full-width step holds one copy of them, not two
    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, batch):
        # runs once per trace: says which path each attention call took
        before = collections.Counter(attention_paths())
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        # stats: the held expert layers' load counters, {} without them
        (loss, stats), grads = jax.value_and_grad(
            lambda p: train_loss_and_stats(cfg, p, batch),
            has_aux=True)(state["params"])
        lr = lr_fn(state["opt"]["step"])
        params, opt, gnorm = adamw_update(state["params"], grads,
                                          state["opt"], lr)
        traced = collections.Counter(attention_paths()) - before
        print(f"attention paths: {json.dumps(dict(sorted(traced.items())))}",
              file=sys.stderr, flush=True)
        return ({"params": params, "opt": opt,
                 "step": state["step"] + 1},
                {"loss": loss, "gnorm": gnorm, "lr": lr, **stats})
    return step


# widths of the reduced config when only some of them are given
_REDUCED = {"d_model": 256, "layers": 4, "vocab": 2048}


def model_config(base, d_model=None, layers=None, vocab=None):
    """``base`` as published when no width is given; otherwise its
    reduced same-family config (float32, no remat) at the given widths,
    the others taking ``_REDUCED``'s."""
    if d_model is None and layers is None and vocab is None:
        return base
    d = d_model or _REDUCED["d_model"]
    return base.reduced(n_layers=layers or _REDUCED["layers"],
                        d_model=d, d_ff=d * 4,
                        vocab=vocab or _REDUCED["vocab"],
                        n_heads=max(4, d // 64))


def device_line() -> str:
    devs = jax.devices()
    return (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")


def run_backend(cfg, strat, backend: str, tokens: int):
    """One real training step of ``strat`` on the proxy program of
    ``cfg`` over ``tokens`` tokens, on the named backend.  Returns
    (executor, RunResult, batch)."""
    from repro import tune
    from repro.runtime.executor import make_executor
    prog, _ = tune.build_strategy_program(cfg, strat, tokens)
    # the proxy compiles against ShapeDtypeStructs; real execution
    # materializes them from fixed seeds, so every backend sees the same
    # params and batch
    batch = tune.synth_batch(prog)
    params = tune.materialize_params(prog.params)
    ex = make_executor(backend, prog, params=params)
    return ex, ex.run(batch), batch


class _ProgramLoader:
    """Deterministic, exactly-resumable batch stream for an arbitrary
    compiled program: batches are a pure function of (seed, step) over
    ``CompiledProgram.input_shapes()`` — the elastic demo's stand-in for
    the token pipeline (same ``state_dict`` contract)."""

    def __init__(self, shapes: dict, vocab: int, seed: int = 0) -> None:
        import numpy as np
        from repro.data import DataState
        self._np = np
        self.shapes = dict(sorted(shapes.items()))
        self.vocab = vocab
        self.state = DataState(seed=seed)

    def next_batch(self) -> dict:
        np = self._np
        rng = np.random.Generator(np.random.Philox(
            key=self.state.seed, counter=[0, 0, 2, self.state.step]))
        batch = {}
        for name, (shape, dtype) in self.shapes.items():
            dt = np.dtype(dtype)
            if np.issubdtype(dt, np.integer):
                batch[name] = rng.integers(
                    0, self.vocab, size=shape).astype(dt)
            else:
                batch[name] = rng.standard_normal(shape).astype(dt)
        self.state.step += 1
        return batch

    def state_dict(self) -> dict:
        return self.state.to_dict()

    def load_state_dict(self, d: dict) -> None:
        from repro.data import DataState
        self.state = DataState.from_dict(d)


def run_elastic(prog, params, vocab: int, args, schedule=None) -> int:
    """The --elastic demo: train, lose a rank, shrink, resume.  With a
    --chaos schedule, the scripted faults replace the single kill and
    the supervisor additionally regrows on arrivals, rewinds on NaN
    spikes, skips corrupted checkpoints and rebalances microbatches."""
    import shutil
    import tempfile

    from repro.checkpoint import CheckpointManager
    from repro.ft import (ChaosInjector, ElasticError, ElasticSupervisor,
                          RankFailureInjector)

    world = prog.strategy.mesh.n_devices
    n_steps = args.elastic_steps
    loader = _ProgramLoader(prog.input_shapes(), vocab, seed=17)

    if schedule is not None:
        injector = ChaosInjector(schedule)
        what = (f"chaos schedule: {len(schedule.events)} events "
                f"{schedule.kinds()} seed={schedule.seed}")
    else:
        fail_at = (args.elastic_fail_at
                   if args.elastic_fail_at is not None
                   else max(1, n_steps // 2))
        rank = (args.elastic_kill_rank
                if args.elastic_kill_rank is not None else world - 1)
        injector = RankFailureInjector({fail_at: rank})
        what = f"rank {rank} dies at step {fail_at}"

    # the registry's runner-factory shape IS the supervisor's contract:
    # factory(prog, params, physical_devices) -> executor
    from repro.runtime.executor import executor_factory, get_backend_spec
    caps = get_backend_spec(args.backend).capabilities
    opts = {"track_memory": False} if caps.memory_ledgers else {}
    runner_factory = executor_factory(args.backend, **opts)

    ckpt_dir = tempfile.mkdtemp(prefix="repro_elastic_")
    try:
        sup = ElasticSupervisor(
            prog, CheckpointManager(ckpt_dir, keep=4, async_save=False),
            loader, runner_factory=runner_factory,
            checkpoint_every=args.elastic_ckpt_every,
            injector=injector, rebalance=schedule is not None)
        print(f"elastic[{args.backend}] world={world} steps={n_steps} "
              f"({what}, checkpoint every {args.elastic_ckpt_every})")
        t0 = time.time()
        try:
            sup.run(params, n_steps, log_every=1)
        except ElasticError as e:
            print(f"elastic: {e}")
            return 2
        wall = time.time() - t0
        for r in sup.reports:
            if r.shrunk_axis:
                print(f"elastic: recovered from rank {r.failed_rank} "
                      f"loss — world {r.old_world}->{r.new_world} "
                      f"(shrunk {r.shrunk_axis}), {r.steps_lost} steps "
                      f"lost, recovery {r.recovery_seconds:.2f}s "
                      f"(compile {r.compile_seconds:.2f}s, "
                      f"cache_hit={r.cache_hit})")
            else:
                print(f"elastic: numerical rewind at step "
                      f"{r.step_failed} — {r.steps_lost} steps lost")
        for g in sup.growths:
            print(f"elastic: regrew world {g.old_world}->{g.new_world} "
                  f"(grew {g.grown_axis}) at step {g.step}, "
                  f"{g.steps_lost} steps lost")
        for b in sup.rebalances:
            print(f"elastic: rebalanced microbatches at step {b.step}: "
                  f"{b.split}")
        if schedule is not None:
            report = sup.chaos_report(n_steps, wall_seconds=wall)
            if args.chaos_report:
                out = pathlib.Path(args.chaos_report)
                out.parent.mkdir(parents=True, exist_ok=True)
                out.write_text(report.to_json())
                print(f"elastic: chaos report written to {out}")
            print(f"elastic: chaos summary — "
                  f"{len(report.recoveries)} recoveries, "
                  f"{len(report.growths)} regrowths, "
                  f"{len(report.rebalances)} rebalances, "
                  f"{report.numeric_rewinds} NaN rewinds, "
                  f"{report.corrupt_detected} corrupt checkpoints "
                  f"skipped, {report.steps_lost_total} total steps "
                  f"lost, final world {report.final_world}")
            return 0
        if not sup.reports:
            print("elastic: no failure fired (check --elastic-fail-at)")
            return 2
        return 0
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    # widths default to the config's own; giving any of them trains the
    # reduced config instead (the others default to _REDUCED)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--resume", action="store_true")
    # declarative Strategy API (repro.core.strategy): replay a saved
    # strategy JSON — validate it, compile the full config's proxy
    # program through compile_training(strategy=...), and report the
    # simulator-predicted step time / peak memory before training
    ap.add_argument("--strategy", default=None, metavar="JSON",
                    help="path to a Strategy JSON document "
                    "(e.g. the strategy.json --autotune saves)")
    from repro.runtime.executor import backends_help, list_backends
    ap.add_argument("--backend", default=None,
                    choices=list(list_backends()),
                    help="execute one real training step of the "
                    "replayed --strategy on the config's proxy program "
                    "(same widths as training, --batch x --seq tokens) "
                    "on the named runtime backend — "
                    + backends_help())
    # elastic fault tolerance (repro.ft.elastic): run a short training
    # loop on the replayed --strategy, kill a rank mid-run, and let the
    # supervisor shrink the mesh, recompile, restore and resume
    ap.add_argument("--elastic", action="store_true",
                    help="with --strategy and --backend: train a few "
                    "steps, kill one rank mid-run, and recover by "
                    "recompiling the same strategy for the shrunk mesh "
                    "(docs/elasticity.md has a quickstart)")
    ap.add_argument("--chaos", default=None, metavar="JSON",
                    help="path to a FaultSchedule JSON document "
                    "(docs/elasticity.md) scripting kills, arrivals, "
                    "stragglers, checkpoint corruption and NaN spikes; "
                    "implies --elastic (needs --strategy and --backend)")
    ap.add_argument("--chaos-report", default=None, metavar="PATH",
                    help="with --chaos: write the run's ChaosReport "
                    "JSON here")
    ap.add_argument("--elastic-steps", type=int, default=8)
    ap.add_argument("--elastic-fail-at", type=int, default=None,
                    help="step at which the rank dies "
                    "(default: elastic-steps // 2)")
    ap.add_argument("--elastic-kill-rank", type=int, default=None,
                    help="which logical rank dies (default: last)")
    ap.add_argument("--elastic-ckpt-every", type=int, default=3)
    # strategy autotuner (repro.tune): pick PP schedule / microbatches /
    # ZeRO / EP for the FULL config before training the reduced one
    ap.add_argument("--autotune", action="store_true",
                    help="search the strategy space for the full config "
                    "and print/save the winning plan before training")
    ap.add_argument("--tune-pp", type=int, default=4)
    ap.add_argument("--tune-dp", type=int, default=2)
    ap.add_argument("--tune-budget-gb", type=float, default=None,
                    help="per-device HBM budget in GiB (default: none)")
    ap.add_argument("--memory-budget", type=float, default=None,
                    metavar="GIB",
                    help="per-device memory budget in GiB, enforced on "
                    "both paths: a --strategy whose estimated peak "
                    "exceeds it is rejected, and --autotune only "
                    "considers candidates that fit (supersedes "
                    "--tune-budget-gb; sweep Remat policies via "
                    "tune.SearchSpace(remat_policies=...))")
    ap.add_argument("--tune-tokens", type=int, default=None,
                    help="global tokens/step for the tuner (default: "
                    "repro.tune.DEFAULT_TOKENS)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    base = get_config(args.arch)
    cfg = model_config(base, args.d_model, args.layers, args.vocab)
    budget_bytes = None
    if args.memory_budget is not None:
        budget_bytes = int(args.memory_budget * 2**30)
    elif args.tune_budget_gb is not None:
        budget_bytes = int(args.tune_budget_gb * 2**30)

    if args.backend and not args.strategy:
        ap.error("--backend needs a --strategy document to execute")
    chaos_schedule = None
    if args.chaos:
        from repro.ft import ChaosScheduleError, FaultSchedule
        try:
            chaos_schedule = FaultSchedule.from_json(
                pathlib.Path(args.chaos).read_text())
        except (ChaosScheduleError, OSError) as e:
            print(f"chaos: {e}")
            return 2
        args.elastic = True
    if args.elastic and not (args.strategy and args.backend):
        ap.error("--elastic needs --strategy and --backend "
                 f"(one of: {', '.join(list_backends())})")

    if args.strategy:
        from repro import tune
        from repro.core.strategy import Strategy, StrategyError
        # parse before anything touches jax devices: --backend spmd must
        # fake the mesh's host device count before the backend locks it
        try:
            strat = Strategy.from_json(
                pathlib.Path(args.strategy).read_text())
        except (StrategyError, OSError) as e:
            print(f"strategy: {e}")
            return 2
        from repro.runtime.executor import get_backend_spec
        backend_caps = (get_backend_spec(args.backend).capabilities
                        if args.backend else None)
        if backend_caps is not None and backend_caps.real_xla \
                and jax.config.jax_platforms == "cpu":
            # on the CPU, a real-XLA backend must fake the mesh's host
            # device count BEFORE anything touches jax devices
            # (capability flag, not a backend-name compare); any other
            # platform runs on its real devices
            if strat.mesh is None:
                print(f"strategy: --backend {args.backend} needs a "
                      "structured strategy with a Mesh (mesh-less "
                      "documents have no device count to fake)")
                return 2
            from repro.launch.hostdevices import ensure_host_devices
            if backend_caps.multi_controller:
                # multi-controller transports block inside host
                # callbacks; async CPU dispatch would let parked ranks
                # starve their peers' programs (runtime/mpmd.py,
                # _ensure_sync_cpu_dispatch).  Cheapest here, before
                # the client exists — the executor rebuilds the client
                # otherwise
                jax.config.update("jax_cpu_enable_async_dispatch",
                                  False)
            n_dev = strat.mesh.n_devices
            if chaos_schedule is not None:
                # arrivals name physical device indices beyond the
                # original world — fake enough host devices for them
                for ev in chaos_schedule.events:
                    for d in ev.devices:
                        n_dev = max(n_dev, int(d) + 1)
            ensure_host_devices(n_dev)
        tokens = args.tune_tokens or tune.DEFAULT_TOKENS
        try:
            prog, sm = tune.build_strategy_program(base, strat, tokens)
        except (StrategyError, ValueError, OSError) as e:
            print(f"strategy: {e}")
            return 2
        score = tune.score_strategy(base, strat, tokens=tokens,
                                    budget_bytes=budget_bytes,
                                    program=(prog, sm))
        print(f"strategy[{base.name}] {strat.label()}  "
              f"step={score.step_seconds*1e3:.2f}ms  "
              f"peak={score.peak_bytes/2**30:.2f}GiB  "
              f"({prog.stats['chunks']} chunks, "
              f"{prog.stats['comms']} comms, "
              f"{prog.stats['devices']} devices)")
        if not score.feasible:
            print(f"strategy: estimated peak {score.peak_bytes/2**30:.2f}"
                  f"GiB exceeds --memory-budget "
                  f"{budget_bytes/2**30:.2f}GiB — pick a higher-Remat/"
                  "lower-mb strategy or raise the budget")
            return 2

        if args.backend:
            # one REAL training step of the same strategy document, on
            # the proxy program of the config being trained
            tokens_exec = args.batch * args.seq
            if args.elastic:
                prog2, _ = tune.build_strategy_program(cfg, strat,
                                                       tokens_exec)
                return run_elastic(prog2,
                                   tune.materialize_params(prog2.params),
                                   cfg.vocab, args,
                                   schedule=chaos_schedule)
            ex, res, batch = run_backend(cfg, strat, args.backend,
                                         tokens_exec)
            if backend_caps.measured_time:
                ms = ex.measure(batch, reps=3) * 1e3
                print(f"backend[{args.backend}] loss={res.loss:.6f}  "
                      f"measured_step={ms:.2f}ms on "
                      f"{res.stats['devices']} devices, {device_line()} "
                      f"({res.stats['tasks']} plan tasks)")
            else:
                print(f"backend[{args.backend}] loss={res.loss:.6f}  "
                      f"peak={res.max_peak()/2**20:.2f}MiB "
                      f"({res.stats['tasks']} plan tasks)")
            return 0

    if args.autotune:
        from repro import tune
        mesh = tune.MeshSpec(pp=args.tune_pp, dp=args.tune_dp)
        budget = budget_bytes
        tokens = args.tune_tokens or tune.DEFAULT_TOKENS
        try:
            plan = tune.search(base, mesh, budget, tokens=tokens)
        except tune.NoFeasiblePlanError as e:
            print(f"autotune: {e}")
            print("autotune: raise --tune-budget-gb, --tune-pp/--tune-dp,"
                  " or shrink the model")
            return 2
        print(plan.summary())
        plan_path = pathlib.Path(args.ckpt_dir) / base.name / "plan.json"
        plan_path.parent.mkdir(parents=True, exist_ok=True)
        import json
        plan_path.write_text(json.dumps(plan.to_dict(), indent=1))
        strat_path = plan_path.with_name("strategy.json")
        strat_path.write_text(plan.strategy().to_json())
        print(f"plan saved to {plan_path} "
              f"({len(plan.directives())} directives); winning strategy "
              f"saved to {strat_path} (replay with --strategy)")
    n_params = cfg.param_count()
    print(f"arch={cfg.name} ({cfg.family}) "
          f"{'as published' if cfg is base else 'reduced'}: "
          f"{n_params/1e6:.1f}M params, {cfg.n_layers} layers, "
          f"d_model={cfg.d_model}, vocab={cfg.vocab}, {cfg.dtype}, "
          f"remat={cfg.remat}; {args.steps} steps "
          f"batch={args.batch} seq={args.seq}; {device_line()}")

    params = init(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    lr_fn = (wsd_schedule(args.lr, args.steps)
             if "minicpm" in args.arch else
             cosine_schedule(args.lr, args.steps))
    step_fn = build_step(cfg, lr_fn)

    loader = TokenLoader(SyntheticTokenSource(cfg.vocab, seed=17),
                         batch=args.batch, seq=args.seq)
    ckpt = CheckpointManager(pathlib.Path(args.ckpt_dir) / cfg.name,
                             keep=2)
    sup = Supervisor(ckpt, loader, checkpoint_every=args.ckpt_every,
                     injector=FailureInjector(tuple(args.fail_at)))

    if args.resume and ckpt.latest_step() is not None:
        state, extra = ckpt.restore(state)
        loader.load_state_dict(extra["data"])
        print(f"resumed from step {extra['step']}")

    t0 = time.time()
    state = sup.run(state, step_fn, args.steps)
    wall = time.time() - t0
    losses = [h["loss"] for h in sup.history]
    print(f"done: {len(sup.history)} steps in {wall:.1f}s — "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"restarts={sup.restarts}, stragglers={len(sup.watchdog.events)}")
    # the first step compiles; the median of the rest is the step time
    steady = [h["dt"] for h in sup.history[1:]]
    if steady:
        print(f"step_time_median={statistics.median(steady)*1e3:.3f}ms "
              f"over {len(steady)} steps after a warm-up step, dispatch "
              f"to device ready (the loader excluded)")
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        print(f"peak_bytes_in_use={stats['peak_bytes_in_use']}")
    if not all(math.isfinite(x) for x in losses):
        print("train: a loss was not finite")
        return 1
    if not losses[-1] < losses[0]:
        print("train: the loss did not decrease")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
