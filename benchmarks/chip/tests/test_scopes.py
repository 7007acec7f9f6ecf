"""The program's scopes and host spans read from a trace (``scopes.py``
and the metric readers over it): the xplane metadata decoder on the
trace of ``test_trace.py``, scope matching on synthetic name paths, the
interval arithmetic on synthetic intervals, and every reader on a trace
recorded on one TPU v5e chip (``fixtures/scopes_one_chip.xplane.pb.xz``,
made by ``record_scopes_fixture.py``: five steps of a small model of the
program, scoped, under ``Supervisor.run``).

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import lzma
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import cellspec  # noqa: E402
import scopes  # noqa: E402
import tracereduce as tr  # noqa: E402

FIXTURES = HERE / "fixtures"
READERS = ("attn_device_ms_per_step", "mlp_device_ms_per_step",
           "lm_head_ce_device_ms_per_step", "optimizer_device_ms_per_step",
           "unscoped_device_share", "data_block_idle_ms_per_step",
           "loop_metrics_idle_ms_per_step", "loop_sync_idle_ms_per_step")


def reader(name):
    return cellspec.load_plugin("metrics", name)


def test_decoder_reads_tf_op_of_the_convolution_fusions():
    tf = scopes.tf_ops(str(FIXTURES / "one_chip.xplane.pb"))
    assert sorted(tf) == [0]
    fusions = {tr.parse_hlo_event(name)[0]: op
               for name, op in tf[0].items()}
    # the step's two matmuls, x @ w and x.T @ h, each fused
    assert fusions == {"multiply_reduce_fusion": "jit(step)/dot_general:",
                       "fusion": "jit(step)/dot_general:"}


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(step)/jvp()/while/body/closed_call/attn/dot_general:", "attn"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general:", "mlp"),
    ("jit(step)/transpose(jvp(lm_head_ce))/dot_general:", "lm_head_ce"),
    ("jit(step)/adamw/mul:", "adamw"),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice:", None),
    ("jit(step)/jvp()/attn_bias/add:", None),
    ("jit(attn)/add", "attn"),
    ("jit(step)/mlp/attn/dot_general:", "attn"),
    ("", None),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def sop(scope, start, end, opcode="fusion"):
    return scopes.ScopedOp(scope, opcode, float(start), float(end))


def synthetic_trace():
    """Two devices over a window [0, 100), two steps, with the program's
    spans on the host."""
    d0 = [sop("attn", 0, 20), sop("attn", 15, 30),  # overlap: union 30
          sop(None, 30, 40),                        # scan copy
          sop(None, 0, 60, "while"),                # holds the above
          sop("mlp", 50, 60), sop("lm_head_ce", 60, 70),
          sop("adamw", 80, 85), sop("adamw", 95, 120)]
    d1 = [sop("attn", 10, 40), sop("mlp", 40, 70), sop(None, 70, 80)]
    spans = [("window", 0, 100),
             ("data.block", -10, 5), ("data.block", 72, 90),
             ("data.block", 100, 110),               # after the window
             ("ft.metrics", 85, 95), ("ft.sync", 20, 80)]
    return scopes.ScopeTrace(devices={0: d0, 1: d1}, spans=spans)


def test_reduce_synthetic():
    red = scopes.reduce(synthetic_trace())
    assert red.window_ns == 100 and red.n_devices == 2
    # device 0 busy [0,70) + [80,85) + [95,100) = 80; device 1 [10,80) = 70
    assert red.busy_ns == pytest.approx(75)
    assert red.scope_busy_ns == pytest.approx(
        {"attn": (30 + 30) / 2, "mlp": (10 + 30) / 2,
         "lm_head_ce": 10 / 2, "adamw": (5 + 5) / 2})
    # the while's [0,60) is busy, but only [30,50) of it under no scope;
    # device 1's [70,80)
    assert red.unscoped_ns == pytest.approx((20 + 10) / 2)
    assert red.n_scoped_ops == 6 + 2
    # spans that start in the window: the second data.block only
    assert red.span_count["data.block"] == 1
    assert red.span_ns["data.block"] == 18
    # device 0 idle [70,80), [85,95); device 1 idle [0,10), [80,100)
    assert red.idle_under_ns["data.block"] == pytest.approx(
        (8 + 5 + 10) / 2)
    assert red.idle_under_ns["ft.metrics"] == pytest.approx((10 + 10) / 2)
    assert red.idle_under_ns["ft.sync"] == pytest.approx((10 + 0) / 2)


def test_intersect():
    assert scopes.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]
    assert scopes.intersect([(0, 10)], []) == []


def readings(reading, steps):
    return {"scopes": reading, "out": {"steps": steps}}


def test_readers_refuse_what_they_cannot_read(capsys):
    for name in READERS:
        assert reader(name).read({"out": {"steps": 3}}) is None
    red = scopes.reduce(synthetic_trace())
    # a window of 3 steps holds one data.block span: off by more than one
    assert reader("data_block_idle_ms_per_step").read(
        readings(red, 3)) is None
    assert reader("data_block_idle_ms_per_step").read(
        readings(red, 2)) == pytest.approx(11.5e-6 / 2)
    # a program compiled without its scopes, or traced from a cache
    # keyed without them: no op carries one
    bare = scopes.ScopeTrace(
        devices={0: [sop(None, 0, 50)]}, spans=[("window", 0, 100)])
    red = scopes.reduce(bare)
    assert red.n_scoped_ops == 0
    for name in READERS[:5]:
        assert reader(name).read(readings(red, 1)) is None
    err = capsys.readouterr().err
    assert "no op in the window carries a scope" in err
    assert "'data.block' spans in a window of 3 steps" in err


def test_readers_on_a_recorded_trace(tmp_path):
    path = tmp_path / "scopes_one_chip.xplane.pb"
    path.write_bytes(lzma.decompress(
        (FIXTURES / "scopes_one_chip.xplane.pb.xz").read_bytes()))
    red = scopes.read(str(path))
    assert red.n_devices == 1
    # five steps, each with its spans, inside the window
    for name in scopes.PROGRAM_SPANS:
        assert red.span_count[name] == 5, name
    values = {name: reader(name).read(readings(red, 5))
              for name in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert all(red.scope_busy_ns[s] > 0 for s in scopes.SCOPES)
    # each op in at most one scope: the scopes add up to no more than
    # the busy time, and with the unscoped time to no less
    scoped = sum(red.scope_busy_ns.values())
    assert scoped <= red.busy_ns <= scoped + red.unscoped_ns * (1 + 1e-9)
    assert 0 < values["unscoped_device_share"] < 100
    # idle under a span lies inside the span
    for span, name in (("data.block", "data_block_idle_ms_per_step"),
                       ("ft.metrics", "loop_metrics_idle_ms_per_step"),
                       ("ft.sync", "loop_sync_idle_ms_per_step")):
        assert values[name] <= red.span_ns[span] * 1e-6 / 5, name
