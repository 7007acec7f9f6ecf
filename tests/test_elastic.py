"""Elastic fault tolerance (ft/elastic.py, DESIGN.md §13).

Fast in-process tests: mesh-shrink planner policy, rank-failure
injection, straggler watchdog -> microbatch rebalance hook, and a full
elastic recovery loop on the reference Interpreter with bit-exact
resume parity.

Kill-a-rank subprocess grid (markers slow + elastic; CI job
tier1-elastic): 8 faked host XLA devices run the real SPMD executor,
one rank dies mid-run, the supervisor shrinks the mesh / recompiles /
restores the checkpoint + stream position / resumes on the surviving
devices — and the resumed run must match an uninterrupted run that
restored the same checkpoint onto the same shrunk mesh, bit for bit in
fp64, across {1f1b, gpipe} x ZeRO{0, 3}.
"""
import json
import pathlib

import jax
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")

from helpers import inputs_spec, make_mlp_forward, make_mlp_params

from repro.checkpoint import CheckpointManager
from repro.core.compiler import compile_training
from repro.core.strategy import Mesh, Pipeline, Strategy, ZeRO
from repro.data import SyntheticVectorSource, VectorLoader
from repro.ft import (ElasticError, ElasticSupervisor, RankFailure,
                      RankFailureInjector, StragglerWatchdog,
                      shrink_for_survivors, sgd_update, zero_shard_degree)
from repro.runtime import Interpreter
from repro.tune import rebalance_microbatches

_ROOT = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# ZeRO shard remap of whole trees
# ---------------------------------------------------------------------------

class TestReshardTree:
    @pytest.mark.parametrize("dtype", ["float32", "float64", "bfloat16",
                                       "int32"])
    @pytest.mark.parametrize("old,new", [(1, 1), (2, 3), (4, 1)])
    def test_keeps_dtype_and_bits(self, dtype, old, new):
        """A reshard is a placement change: each leaf comes back with
        its own dtype and bytes, 64-bit leaves included while x64 is
        off."""
        from repro.checkpoint import reshard_tree
        rng = np.random.default_rng(0)
        leaf = rng.standard_normal((5, 3)).astype(
            jax.numpy.dtype(dtype))
        out = reshard_tree({"w": leaf, "b": leaf.ravel()[:1]}, old, new)
        for got, want in ((out["w"], leaf), (out["b"], leaf.ravel()[:1])):
            assert np.asarray(got).dtype == want.dtype
            assert np.asarray(got).tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# mesh-shrink planner
# ---------------------------------------------------------------------------

class TestShrinkPlanner:
    def _strat(self, sched="1f1b", pp=4, dp=2, zero=3):
        mesh = Mesh(pp=pp, dp=dp)
        return Strategy(mesh, Pipeline(sched, n_mb=4)
                        | ZeRO(stage=zero)).validate()

    def test_prefers_dp_shrink(self):
        plan = shrink_for_survivors(self._strat(), range(7))
        assert plan.shrunk_axis == "dp"
        assert plan.new_mesh == Mesh(pp=4, dp=1)
        assert plan.strategy.mesh == plan.new_mesh

    def test_largest_world_wins(self):
        # 6 survivors: dp 2->1 (world 4) beats any pp shrink (pp=2 also
        # world 4 but dp is preferred; pp=1 is world 2)
        plan = shrink_for_survivors(self._strat(), range(6))
        assert plan.new_mesh.n_devices == 4
        assert plan.shrunk_axis == "dp"

    def test_pp_shrink_requires_stage_divisibility(self):
        # S is pinned to 8 (2 * pp under the OLD mesh): pp'=3 invalid
        # (8 % 3), pp'=2 valid -> with 3 survivors the best is pp=1,dp=2
        plan = shrink_for_survivors(self._strat(), range(3))
        assert plan.shrunk_axis == "pp"
        assert plan.new_mesh == Mesh(pp=1, dp=2)
        # stage count is pinned, so 8 stages now live on 1 rank
        assert plan.strategy.pipeline.n_stages == 8

    def test_plan_depends_only_on_survivor_count(self):
        a = shrink_for_survivors(self._strat(), [0, 1, 2, 3, 4, 5, 6])
        b = shrink_for_survivors(self._strat(), [1, 2, 3, 4, 5, 6, 7])
        assert a.new_mesh == b.new_mesh and a.shrunk_axis == b.shrunk_axis

    def test_dualpipev_cannot_shrink_pp(self):
        # dualpipev pins S == 2*pp; S is pinned to the old value, so any
        # pp' != pp is invalid and only dp can shrink
        strat = self._strat(sched="dualpipev")
        plan = shrink_for_survivors(strat, range(7))
        assert plan.shrunk_axis == "dp"
        with pytest.raises(ElasticError):
            # dp already 1 after one shrink; only pp reductions remain,
            # all invalid for dualpipev
            shrink_for_survivors(plan.strategy, range(3))

    def test_errors(self):
        strat = self._strat()
        with pytest.raises(ElasticError):
            shrink_for_survivors(strat, [])
        with pytest.raises(ElasticError):  # nothing to shrink
            shrink_for_survivors(strat, range(8))

    def test_zero_shard_degree(self):
        assert zero_shard_degree(self._strat(zero=3)) == 2
        assert zero_shard_degree(self._strat(zero=2)) == 2
        assert zero_shard_degree(self._strat(zero=1)) == 1
        assert zero_shard_degree(self._strat(zero=0)) == 1


class TestRankFailureInjector:
    def test_fires_once_with_rank(self):
        inj = RankFailureInjector({3: 1})
        inj.check(2)
        with pytest.raises(RankFailure) as ei:
            inj.check(3)
        assert ei.value.rank == 1 and ei.value.step == 3
        inj.check(3)  # second pass: already fired


# ---------------------------------------------------------------------------
# straggler watchdog -> microbatch rebalance
# ---------------------------------------------------------------------------

class TestWatchdogRebalance:
    def test_no_false_positive_on_uniform_trace(self):
        wd = StragglerWatchdog(threshold=2.0)
        rng = np.random.default_rng(0)
        flagged = []
        for step in range(50):
            for rank in range(4):
                # +-5% jitter around a common step time
                dt = 0.1 * (1 + 0.05 * rng.standard_normal())
                if wd.observe_rank(rank, step, dt):
                    flagged.append((step, rank))
        assert flagged == []
        assert wd.rank_events == []
        slow = wd.slowdowns()
        assert set(slow) == {0, 1, 2, 3}
        assert all(abs(v - 1.0) < 0.2 for v in slow.values())

    def test_detects_persistent_straggler(self):
        wd = StragglerWatchdog(threshold=2.0)
        for step in range(20):
            for rank in range(4):
                wd.observe_rank(rank, step, 0.3 if rank == 2 else 0.1)
        assert any(rank == 2 for (_, rank, _, _) in wd.rank_events)
        assert all(rank == 2 for (_, rank, _, _) in wd.rank_events)
        slow = wd.slowdowns()
        assert slow[2] > 2.5
        assert abs(slow[0] - 1.0) < 0.05

    def test_ema_feeds_rebalance(self):
        wd = StragglerWatchdog()
        for step in range(20):
            for rank in range(4):
                wd.observe_rank(rank, step, 0.3 if rank == 2 else 0.1)
        counts = rebalance_microbatches(8, wd.slowdowns())
        assert sum(counts.values()) == 8
        # the 3x straggler gets the smallest share
        assert counts[2] == min(counts.values())
        assert counts[2] < counts[0]

    def test_rebalance_uniform_guard(self):
        # within-threshold spread -> exactly uniform split
        assert rebalance_microbatches(8, {0: 1.0, 1: 1.1, 2: 0.95,
                                          3: 1.05}) == \
            {0: 2, 1: 2, 2: 2, 3: 2}
        # remainder goes to the fastest ranks
        counts = rebalance_microbatches(7, {0: 1.0, 1: 1.1, 2: 0.95})
        assert sum(counts.values()) == 7
        assert counts[2] == 3  # fastest
        assert counts[1] == 2

    def test_rebalance_proportional(self):
        counts = rebalance_microbatches(12, {0: 1.0, 1: 2.0})
        assert sum(counts.values()) == 12
        assert counts[0] == 8 and counts[1] == 4  # 2:1 speed ratio

    def test_rebalance_errors(self):
        with pytest.raises(ValueError):
            rebalance_microbatches(4, {})
        with pytest.raises(ValueError):
            rebalance_microbatches(4, {0: 0.0})
        with pytest.raises(ValueError):
            rebalance_microbatches(-1, {0: 1.0})


# ---------------------------------------------------------------------------
# fast in-process elastic recovery (reference Interpreter)
# ---------------------------------------------------------------------------

S, D, BATCH = 4, 16, 8


def _interp_factory(prog, params, devices):
    # the Interpreter simulates devices; physical mapping is a no-op
    return Interpreter(prog, params=params, track_memory=False)


def _compile(sched="1f1b", zero=3, n_mb=2):
    mesh = Mesh(pp=2, dp=2)
    strat = Strategy(mesh, Pipeline(sched, n_mb=n_mb)
                     | ZeRO(stage=zero)).validate()
    params = make_mlp_params(jax.random.PRNGKey(0), S, d=D)
    prog = compile_training(make_mlp_forward(S), params,
                            inputs_spec(BATCH, D), strategy=strat)
    return prog, params


def _bits(x) -> bytes:
    return np.asarray(x).tobytes()


def _params_bits(tree) -> list:
    return [_bits(l) for l in jax.tree_util.tree_leaves(tree)]


class TestElasticSupervisorFast:
    def _run_elastic(self, tmp_path, *, fail_at=5, rank=3, n_steps=8,
                     every=3, seed=7):
        prog, params = _compile()
        loader = VectorLoader(SyntheticVectorSource(D, seed=seed),
                              batch=BATCH)
        ckpt = CheckpointManager(tmp_path, keep=10, async_save=False)
        sup = ElasticSupervisor(
            prog, ckpt, loader, runner_factory=_interp_factory,
            checkpoint_every=every,
            injector=RankFailureInjector({fail_at: rank}))
        final = sup.run(params, n_steps, log_every=0)
        return prog, params, sup, final, ckpt

    def test_recovery_report_accounting(self, tmp_path):
        _, _, sup, _, _ = self._run_elastic(tmp_path)
        assert len(sup.reports) == 1
        r = sup.reports[0]
        assert r.step_failed == 5 and r.resume_step == 3
        assert r.steps_lost == 2          # bounded by the ckpt interval
        assert r.old_world == 4 and r.new_world == 2
        assert r.failed_rank == 3 and r.shrunk_axis == "dp"
        assert not r.cache_hit
        assert r.recovery_seconds >= r.compile_seconds >= 0
        # post-recovery steps ran on the shrunk world
        worlds = {h["step"]: h["world"] for h in sup.history}
        assert worlds[3] == 4 and worlds[8] == 2

    def test_resume_parity_bitexact_vs_uninterrupted(self, tmp_path):
        prog, params, sup, final, ckpt = self._run_elastic(tmp_path)
        # reference: restore the SAME checkpoint, run the SAME shrunk
        # program uninterrupted — identical restored state + identical
        # program => bit-identical losses and params from step 4 on
        plan = shrink_for_survivors(prog.strategy, [0, 1, 2])
        ref_prog = prog.recompile(strategy=plan.strategy)
        state, extra = ckpt.restore({"params": params}, step=3)
        loader = VectorLoader(SyntheticVectorSource(D, seed=7),
                              batch=BATCH)
        loader.load_state_dict(extra["data"])
        p = state["params"]
        if int(extra["zero_shards"]) != zero_shard_degree(plan.strategy):
            from repro.checkpoint import reshard_tree
            p = reshard_tree(p, int(extra["zero_shards"]),
                             zero_shard_degree(plan.strategy))
        update = sgd_update()
        it = Interpreter(ref_prog, params=p, track_memory=False)
        ref_losses = {}
        for step in range(3, 8):
            res = it.run(loader.next_batch())
            p = update(p, res.grads, step)
            it.params = p
            ref_losses[step + 1] = float(res.loss)
        got = {h["step"]: h["loss"] for h in sup.history}  # last wins
        for step, ref in ref_losses.items():
            assert _bits(np.float64(got[step])) == \
                _bits(np.float64(ref)), f"loss diverged at step {step}"
        assert _params_bits(final) == _params_bits(p)

    def test_failure_before_first_checkpoint_rewinds_stream(
            self, tmp_path):
        prog, params = _compile()
        loader = VectorLoader(SyntheticVectorSource(D, seed=3),
                              batch=BATCH)
        pristine = loader.fingerprint()
        ckpt = CheckpointManager(tmp_path, keep=4, async_save=False)
        sup = ElasticSupervisor(
            prog, ckpt, loader, runner_factory=_interp_factory,
            checkpoint_every=100,   # no checkpoint before the failure
            injector=RankFailureInjector({2: 3}))
        sup.run(params, 4, log_every=0)
        r = sup.reports[0]
        assert r.resume_step == 0 and r.steps_lost == 2
        # the restart consumed the stream from its pristine position:
        # 4 completed steps from a rewound loader leave it at step 4
        assert int(loader.state_dict()["step"]) == 4
        # and the shrunk-world restart really did replay batch 0
        fresh = VectorLoader(SyntheticVectorSource(D, seed=3),
                             batch=BATCH)
        assert pristine == fresh.fingerprint()

    def test_second_failure_hits_plan_cache(self, tmp_path):
        prog, params = _compile()
        loader = VectorLoader(SyntheticVectorSource(D, seed=5),
                              batch=BATCH)
        ckpt = CheckpointManager(tmp_path, keep=10, async_save=False)
        sup = ElasticSupervisor(
            prog, ckpt, loader, runner_factory=_interp_factory,
            checkpoint_every=2,
            injector=RankFailureInjector({3: 3, 6: 1}))
        sup.run(params, 8, log_every=0)
        assert len(sup.reports) == 2
        # 4 -> 2 (shrink dp), then 2 -> 1 (shrink pp: only axis left)
        assert sup.reports[0].new_world == 2
        assert sup.reports[1].new_world == 1
        assert not sup.reports[0].cache_hit
        # different target worlds -> no cache hit; now prewarm and
        # verify a repeat failure at a seen world IS a hit
        sup2_prog, sup2_params = _compile()
        loader2 = VectorLoader(SyntheticVectorSource(D, seed=5),
                               batch=BATCH)
        sup2 = ElasticSupervisor(
            sup2_prog, ckpt, loader2, runner_factory=_interp_factory,
            checkpoint_every=2,
            injector=RankFailureInjector({3: 1}))
        assert sup2.prewarm(1) == 1
        sup2.run(sup2_params, 5, log_every=0)
        assert sup2.reports[0].cache_hit
        assert sup2.reports[0].compile_seconds == 0.0

    def test_failure_budget_exhausts(self, tmp_path):
        prog, params = _compile()
        loader = VectorLoader(SyntheticVectorSource(D, seed=5),
                              batch=BATCH)
        ckpt = CheckpointManager(tmp_path, keep=4, async_save=False)

        class AlwaysFail(RankFailureInjector):
            def check(self, step):
                raise RankFailure(step, 0)

        sup = ElasticSupervisor(
            prog, ckpt, loader, runner_factory=_interp_factory,
            checkpoint_every=2, injector=AlwaysFail(), max_failures=2)
        with pytest.raises(ElasticError, match="budget exhausted"):
            sup.run(params, 8, log_every=0)


# ---------------------------------------------------------------------------
# kill-a-rank on real (faked-host) XLA devices — the SPMD harness
# ---------------------------------------------------------------------------

pytestmark_spmd = [pytest.mark.slow, pytest.mark.elastic]

CHILD = r"""
import json, os, pathlib, sys, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np

from helpers import inputs_spec, make_mlp_forward, make_mlp_params
from repro.checkpoint import CheckpointManager, reshard_tree
from repro.core.compiler import compile_training
from repro.core.strategy import Mesh, Pipeline, Strategy, ZeRO
from repro.data import SyntheticVectorSource, VectorLoader
from repro.ft import (ElasticSupervisor, RankFailureInjector,
                      shrink_for_survivors, sgd_update,
                      zero_shard_degree)
from repro.runtime.spmd import SpmdExecutor

S, D, BATCH = 8, 16, 16
N_STEPS, CKPT_EVERY, FAIL_AT, KILL_RANK = 10, 4, 6, 3

def bits(x):
    return np.asarray(x).tobytes()

def params_bits(tree):
    return [bits(l) for l in jax.tree_util.tree_leaves(tree)]

def spmd_factory(prog, params, devices):
    return SpmdExecutor(prog, params=params, physical_devices=devices)

cases = json.loads(sys.argv[1])
for sched, zero in cases:
    label = f"{sched}/zero{zero}"
    mesh = Mesh(pp=4, dp=2)
    strat = Strategy(mesh, Pipeline(sched, n_mb=4)
                     | ZeRO(stage=zero)).validate()
    params = make_mlp_params(jax.random.PRNGKey(0), S, d=D)
    prog = compile_training(make_mlp_forward(S), params,
                            inputs_spec(BATCH, D), strategy=strat)
    with tempfile.TemporaryDirectory() as td:
        loader = VectorLoader(SyntheticVectorSource(D, seed=11),
                              batch=BATCH)
        ckpt = CheckpointManager(pathlib.Path(td), keep=10,
                                 async_save=False)
        sup = ElasticSupervisor(
            prog, ckpt, loader, runner_factory=spmd_factory,
            checkpoint_every=CKPT_EVERY,
            injector=RankFailureInjector({FAIL_AT: KILL_RANK}))
        final = sup.run(params, N_STEPS, log_every=0)

        assert len(sup.reports) == 1, sup.reports
        r = sup.reports[0]
        assert r.resume_step == 4 and r.step_failed == FAIL_AT
        # resume within one checkpoint interval of lost steps
        assert 0 < r.steps_lost <= CKPT_EVERY, r.steps_lost
        assert r.old_world == 8 and r.new_world == 4
        assert r.shrunk_axis == "dp" and r.failed_rank == KILL_RANK
        # the shrunk program avoided the dead physical device
        assert KILL_RANK not in sup.physical, sup.physical
        assert len(sup.physical) == 4

        # reference: restore the SAME checkpoint onto the SAME shrunk
        # mesh and run uninterrupted
        plan = shrink_for_survivors(
            strat, [x for x in range(8) if x != KILL_RANK])
        ref_prog = prog.recompile(strategy=plan.strategy)
        state, extra = ckpt.restore({"params": params}, step=4)
        assert int(extra["data"]["step"]) == 4, extra["data"]
        rl = VectorLoader(SyntheticVectorSource(D, seed=11),
                          batch=BATCH)
        rl.load_state_dict(extra["data"])
        p = state["params"]
        old_deg, new_deg = (int(extra["zero_shards"]),
                            zero_shard_degree(plan.strategy))
        if old_deg != new_deg:
            p = reshard_tree(p, old_deg, new_deg)
        update = sgd_update()
        ex = SpmdExecutor(ref_prog, params=p)
        ref_losses = {}
        for step in range(4, N_STEPS):
            res = ex.run(rl.next_batch())
            p = update(p, res.grads, step)
            ex.params = p
            ref_losses[step + 1] = float(res.loss)

        got = {h["step"]: h["loss"] for h in sup.history}  # last wins
        for step, ref in ref_losses.items():
            assert bits(np.float64(got[step])) == \
                bits(np.float64(ref)), \
                (label, step, got[step], ref)
        assert params_bits(final) == params_bits(p), label
    print(f"CASE_OK {label}", flush=True)
print("ALL_OK", flush=True)
"""


@pytest.mark.slow
@pytest.mark.elastic
class TestKillARankSpmd:
    """One subprocess runs the whole grid (device-count flag must be set
    before jax initializes; subprocess isolation keeps it from leaking
    into other tests)."""

    def _run_child(self, cases):
        from helpers import run_child_once_retry
        return run_child_once_retry(CHILD, json.dumps(cases),
                                    timeout=600)

    def test_kill_a_rank_grid(self):
        cases = [[sched, zero] for sched in ("1f1b", "gpipe")
                 for zero in (0, 3)]
        out = self._run_child(cases)
        for sched, zero in cases:
            assert f"CASE_OK {sched}/zero{zero}" in out, out
        assert "ALL_OK" in out
