"""The program's scopes, kernels and host spans read from a trace
(``scopes.py`` and the metric readers over it): the xplane metadata
decoder on the trace of ``test_trace.py``, scope paths of synthetic name
paths, the interval arithmetic and a kernel's roofline share on
synthetic intervals, and every reader on a trace recorded on one TPU
v5e chip (``fixtures/scopes_one_chip.xplane.pb.xz``, made by
``record_scopes_fixture.py``: five steps of a small model of the
program, scoped, under ``Supervisor.run``), with readers of a scope and
a kernel that are files of their own.

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import json
import lzma
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import cellspec  # noqa: E402
import scopes  # noqa: E402
import tracereduce as tr  # noqa: E402

CHIP = HERE.parent
FIXTURES = HERE / "fixtures"
SCOPES = ("attn", "mlp", "lm_head_ce", "adamw")
SPANS = ("train_step", "data.block", "ft.sync", "ft.metrics")
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


@pytest.mark.parametrize("tf_op,under", [
    ("jit(step)/jvp()/while/body/closed_call/attn/dot_general:", {"attn"}),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general:", {"mlp"}),
    ("jit(step)/transpose(jvp(lm_head_ce))/dot_general:", {"lm_head_ce"}),
    ("jit(step)/adamw/mul:", {"adamw"}),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice:", set()),
    ("jit(step)/jvp()/attn_bias/add:", set()),
    ("jit(attn)/add", {"attn"}),
    ("jit(step)/mlp/attn/dot_general:", {"mlp", "attn"}),
    ("", set()),
])
def test_scope_of(tf_op, under):
    path = scopes.scope_path(tf_op)
    assert set(path) & set(SCOPES) == under
    # the primitive is no scope
    assert "dot_general" not in path and "add" not in path


def sop(scope, start, end, opcode="fusion", name="fusion.1"):
    """An op under ``scope``; a container (``while``) is under none."""
    path = () if scope is None or opcode == "while" else ("step", scope)
    return scopes.ScopedOp(name, path, float(start), float(end))


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
    red = scopes.ScopeReading(synthetic_trace())
    assert red.window_ns == 100 and red.n_devices == 2
    # device 0 busy [0,70) + [80,85) + [95,100) = 80; device 1 [10,80) = 70
    assert red.busy_ns == pytest.approx(75)
    assert {sc: red.busy_under((sc,)) for sc in SCOPES} == pytest.approx(
        {"attn": (30 + 30) / 2, "mlp": (10 + 30) / 2,
         "lm_head_ce": 10 / 2, "adamw": (5 + 5) / 2})
    # the while's [0,60) is busy, but only [30,50) of it under no scope;
    # device 1's [70,80)
    assert red.unscoped_ns(SCOPES) == pytest.approx((20 + 10) / 2)
    assert red.n_ops_under(SCOPES) == 6 + 2
    # spans that start in the window: the second data.block only
    assert red.span_count("data.block") == 1
    assert red.span_ns("data.block") == 18
    # device 0 idle [70,80), [85,95); device 1 idle [0,10), [80,100)
    assert red.idle_under_ns("data.block") == pytest.approx(
        (8 + 5 + 10) / 2)
    assert red.idle_under_ns("ft.metrics") == pytest.approx((10 + 10) / 2)
    assert red.idle_under_ns("ft.sync") == pytest.approx((10 + 0) / 2)


def test_intersect():
    assert scopes.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]
    assert scopes.intersect([(0, 10)], []) == []


def readings(reading, steps):
    return {"scopes": reading, "out": {"steps": steps}}


def test_readers_refuse_what_they_cannot_read(capsys):
    for name in READERS:
        assert reader(name).read({"out": {"steps": 3}}) is None
    red = scopes.ScopeReading(synthetic_trace())
    # a window of 3 steps holds one data.block span: off by more than one
    assert reader("data_block_idle_ms_per_step").read(
        readings(red, 3)) is None
    assert reader("data_block_idle_ms_per_step").read(
        readings(red, 2)) == pytest.approx(11.5e-6 / 2)
    # a program compiled without its scopes, or traced from a cache
    # keyed without them: no op carries one
    bare = scopes.ScopeTrace(
        devices={0: [sop(None, 0, 50)]}, spans=[("window", 0, 100)])
    red = scopes.ScopeReading(bare)
    assert red.n_ops_under(SCOPES) == 0
    for name in READERS[:5]:
        assert reader(name).read(readings(red, 1)) is None
    err = capsys.readouterr().err
    assert "no op in the window is under ['adamw', 'attn', 'lm_head_ce', " \
        "'mlp']: the program has no such scope, or came from a compile " \
        "cache keyed without its metadata" in err
    assert "'data.block' spans in a window of 3 steps" in err


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The scope reading of the recorded trace."""
    path = tmp_path_factory.mktemp("fixture") / "scopes_one_chip.xplane.pb"
    path.write_bytes(lzma.decompress(
        (FIXTURES / "scopes_one_chip.xplane.pb.xz").read_bytes()))
    return scopes.read(str(path))


def test_readers_on_a_recorded_trace(recorded):
    red = recorded
    assert red.n_devices == 1
    assert reader("unscoped_device_share").SCOPES == SCOPES
    # five steps, each with its spans, inside the window
    for name in SPANS:
        assert red.span_count(name) == 5, name
    values = {name: reader(name).read(readings(red, 5))
              for name in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    busy = {s: red.busy_under((s,)) for s in SCOPES}
    assert all(v > 0 for v in busy.values())
    # each op in at most one scope: the scopes add up to no more than
    # the busy time, and with the unscoped time to no less
    scoped = sum(busy.values())
    assert scoped <= red.busy_ns <= scoped + red.unscoped_ns(SCOPES) * (
        1 + 1e-9)
    assert 0 < values["unscoped_device_share"] < 100
    # idle under a span lies inside the span
    for span, name in (("data.block", "data_block_idle_ms_per_step"),
                       ("ft.metrics", "loop_metrics_idle_ms_per_step"),
                       ("ft.sync", "loop_sync_idle_ms_per_step")):
        assert values[name] <= red.span_ns(span) * 1e-6 / 5, name
    # the recording predates the fused kernels
    assert reader("flash_attention_roofline").read(
        readings(red, 5)) is None


# a reader of a scope, as a later change would add it: a file of its own
SCOPE_READER = """from scopes import scope_ms_per_step

SCOPE = {scope!r}


def read(r):
    return scope_ms_per_step(r, SCOPE)
"""


def new_metrics_dir(tmp_path, name, scope) -> pathlib.Path:
    """A copy of ``metrics/`` with one reader more, for ``scope``."""
    metrics = tmp_path / "metrics"
    shutil.copytree(CHIP / "metrics", metrics,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (metrics / f"{name}.py").write_text(SCOPE_READER.format(scope=scope))
    return metrics


def test_a_new_scope_is_a_new_file(recorded, tmp_path):
    metrics = new_metrics_dir(tmp_path, "moe_device_ms_per_step", "attn")
    new = cellspec.load_plugin("metrics", "moe_device_ms_per_step",
                               tmp_path)
    r = readings(recorded, 5)
    assert new.read(r) == reader("attn_device_ms_per_step").read(r) > 0
    # where the trace holds no op under the scope, nothing is read
    (metrics / "moe_device_ms_per_step.py").write_text(
        SCOPE_READER.format(scope="moe"))
    new = cellspec.load_plugin("metrics", "moe_device_ms_per_step",
                               tmp_path)
    assert new.read(r) is None
    # the unscoped share keeps its meaning: it names its scopes itself,
    # and a new reader beside it changes nothing it reads
    unscoped = cellspec.load_plugin("metrics", "unscoped_device_share",
                                    tmp_path)
    assert unscoped.SCOPES == SCOPES
    assert unscoped.read(r) == reader("unscoped_device_share").read(r) > 0


def test_busy_matching_finds_ops_by_name(recorded):
    assert recorded.busy_matching(r"fusion(\.\d+)?") > 0
    assert recorded.busy_matching("fusion") < recorded.busy_matching(
        r"fusion(\.\d+)?")
    assert recorded.busy_matching("no_such_kernel.*") == 0


def roofline_trace():
    """One device, window [0, 1e9) ns, two steps: the forward kernel
    twice, dK/dV and dQ once a step, overlapping other ops."""
    ms = 1e6
    ops = []
    for step in (0, 500 * ms):
        t = step
        for name, length in (("flash_attention.15", 10), ("fusion.3", 5),
                             ("flash_attention.16", 10),
                             ("flash_mha_bwd_dkv_block_q_major_512_block_q"
                              "_512_block_k_major_51", 20),
                             ("flash_mha_bwd_dq_block_q_major_512_block_k_"
                              "major_512_block_k_512", 15)):
            ops.append(sop("attn", t, t + length * ms, name=name))
            t += length * ms
        # an op beside the dQ kernel adds nothing to its busy union
        ops.append(sop("attn", t - 10 * ms, t, name="broadcast_in_dim.335"))
    return scopes.ScopeTrace(devices={0: ops},
                             spans=[("window", 0, 1000 * ms)])


def test_roofline_share_on_a_synthetic_trace():
    cell = cellspec.load_cell(CHIP.parents[1] / "BENCHMARK.json",
                              "qwen1.5-0.5b.train-s2048-b4")
    peaks = cellspec.peaks_for("TPU v5 lite")
    red = scopes.ScopeReading(roofline_trace())
    # 10 + 10 + 20 + 15 ms of kernels in each of two steps
    assert red.busy_matching(
        cellspec.load_plugin("rooflines", "flash_attention").OPS) == \
        pytest.approx(110e6)
    # 11 matmuls x 24 layers x B 4 x H 16 x S 2048^2 x D 64, the causal
    # half of each: 4.535 TFLOP a step, 23.02 ms at 197 TFLOP/s; the
    # bytes (7.73 GB, 9.43 ms at 819 GB/s) bound it less
    flops = 11 * 24 * 4 * 16 * 2048 * 2048 * 64
    assert flops == 4_535_485_464_576
    share = reader("flash_attention_roofline").read(
        {"scopes": red, "cell": cell, "peaks": peaks, "out": {"steps": 2}})
    assert share == pytest.approx(100 * (flops / 197e12) / 55e-3)
    assert share == pytest.approx(41.86, abs=0.01)


def test_roofline_counts_stay_under_the_kernels_work():
    """The count is the causal half: under the kernels' own work, which
    runs the diagonal's blocks whole, at both cells' shapes."""
    work = cellspec.load_plugin("rooflines", "flash_attention")
    for name in ("qwen1.5-0.5b", "minicpm-2b-8l"):
        c = json.loads((CHIP / "configs" / f"{name}.json").read_text())
        traffic = {"qwen1.5-0.5b": "train-s2048-b4",
                   "minicpm-2b-8l": "train-s4096-b1"}[name]
        t = json.loads((CHIP / "traffic" / f"{traffic}.json").read_text())
        blocks = t["seq"] // 512
        run = 2 * (blocks * (blocks + 1) // 2) * 512 * 512
        per = (c["num_hidden_layers"] * t["batch"]
               * c["num_attention_heads"] * c["head_dim"])
        assert work.flops(c, t) == 11 * per * t["seq"] ** 2
        assert work.flops(c, t) < 11 * per * run
