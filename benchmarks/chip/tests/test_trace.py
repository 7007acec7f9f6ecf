"""The trace reduction, on synthetic intervals and on a trace recorded on
one TPU v5e chip (``fixtures/one_chip.xplane.pb``, made by
``record_fixture.py``: five steps of a small jitted program inside the
benchmark's ``loader``, ``dispatch`` and ``block`` spans).

  PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import tracereduce as tr  # noqa: E402

FIXTURE = HERE / "fixtures" / "one_chip.xplane.pb"


def op(name, opcode, start, end):
    return tr.Op(name, opcode, float(start), float(end))


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [(0, 3), (5, 9)]


def test_subtract_and_gaps():
    busy = [(10, 20), (30, 40)]
    assert tr.gaps(busy, 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert tr.subtract([(0, 100)], [(10, 20), (15, 30), (90, 120)]) == \
        [(0, 10), (30, 90)]
    assert tr.subtract([(5, 6)], [(0, 10)]) == []


def test_parse_hlo_event():
    assert tr.parse_hlo_event(
        "%fusion.3 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), "
        "kind=kLoop") == ("fusion.3", "fusion")
    assert tr.parse_hlo_event(
        "%all-gather-start.1 = (bf16[4]{0}, bf16[16]{0}) "
        "all-gather-start(bf16[4]{0} %x)") == ("all-gather-start.1",
                                               "all-gather-start")
    assert tr.parse_hlo_event("not hlo") == ("not hlo", "not hlo")


def synthetic_trace():
    """Two devices over a window [0, 100): overlapping compute ops, one
    collective half hidden behind compute, and host spans."""
    d0 = tr.DeviceTrace(ops=[
        op("fusion.1", "fusion", 0, 30),
        op("fusion.2", "fusion", 20, 40),          # overlaps fusion.1
        op("all-gather.1", "all-gather", 35, 55),  # 35-40 hidden
        op("convolution.1", "convolution", 70, 80),
        op("while.3", "while", 70, 80),            # holds convolution.1
        op("fusion.1", "fusion", 95, 120),         # runs past the window
    ])
    d1 = tr.DeviceTrace(ops=[op("fusion.1", "fusion", 10, 60),
                             # a rank-gated branch holding the permute
                             op("conditional.4", "conditional", 50, 90)],
                        async_ops=[op("collective-permute-start.2",
                                      "collective-permute-start", 50, 90)])
    spans = [("window", 0, 100), ("loader", 55, 70), ("block", 80, 95)]
    return tr.Trace(devices={0: d0, 1: d1}, spans=spans)


def test_reduce_synthetic():
    red = tr.reduce(synthetic_trace())
    assert red.window_ns == 100 and red.n_devices == 2
    # device 0 busy: [0,55) + [70,80) + [95,100) = 70; device 1: 80
    assert red.busy_ns == pytest.approx(75.0)
    # device 0: the all-gather's 40-55 is exposed (15); device 1: the
    # async permute's 60-90 (30) is under no compute, only under the
    # container that holds it
    assert red.exposed_collective_ns == pytest.approx(22.5)
    top = dict(red.top_ops)
    assert "while.3" not in top
    assert top["fusion.1"] == pytest.approx((30 + 5 + 50) / 2 * 1e-9)
    idle = dict(red.idle_by_label)
    # device 0 gaps: [55,70) under loader, [80,95) under block;
    # device 1: [0,10) under nothing, [90,100) partly under block
    assert idle["loader"] == pytest.approx(15 / 2 * 1e-9)
    assert idle["block"] == pytest.approx((15 + 10) / 2 * 1e-9)
    assert idle["host other"] == pytest.approx(10 / 2 * 1e-9)


def test_reduce_needs_window_and_device():
    t = synthetic_trace()
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(devices=t.devices, spans=[]))
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace(devices={}, spans=t.spans))


def test_recorded_trace():
    trace = tr.load(str(FIXTURE), span_names=("loader", "dispatch",
                                              "block"))
    assert sorted(trace.devices) == [0]
    ops = trace.devices[0].ops
    assert len(ops) == 35
    assert {o.opcode for o in ops} >= {"copy", "copy-start", "copy-done"}
    names = [n for n, _, _ in trace.spans]
    assert names.count("loader") == 5 and names.count("block") == 5
    lo = min(s for n, s, _ in trace.spans if n == "loader")
    hi = max(e for n, _, e in trace.spans if n == "block")
    # host spans and device ops share one clock: every step's ops fall
    # between the first loader and the last block
    assert all(lo < o.start and o.end < hi for o in ops
               if o.opcode != "copy")
    red = tr.reduce(trace, window=(lo, hi))
    assert 0 < red.busy_ns < red.window_ns
    assert red.exposed_collective_ns == 0
    # the host's loader work is what the device waits for
    assert red.idle_by_label[0][0] in ("loader", "dispatch")
    assert red.top_ops[0][1] > 0


def test_step_mfu_is_model_flops_over_busy_time():
    import cellspec
    chip = HERE.parent
    cell = cellspec.load_cell(chip.parents[1] / "BENCHMARK.json",
                              "qwen1.5-0.5b.train-s2048-b4")
    peaks = cellspec.peaks_for("TPU v5 lite")
    # 10 steps of 8192 tokens at 3.388 GFLOP a token in 5 s of device
    # busy time: 277.6 TFLOP over 5 s x 197 TFLOP/s
    red = tr.Reduced(window_ns=6e9, n_devices=1, busy_ns=5e9,
                     exposed_collective_ns=0, top_ops=[], idle_by_label=[])
    out = {"tokens": 10 * 8192, "steps": 10, "lane_info": {}}
    value = cellspec.load_plugin("metrics", "step_mfu").read(
        {"reduced": red, "out": out, "cell": cell, "peaks": peaks})
    fpt = cellspec.flops_per_token(cell, {})
    assert value == pytest.approx(100 * fpt * 81920 / (5 * 197e12))
    assert value == pytest.approx(28.18, abs=0.01)
    # four chips each busy 5 s do four times the work in the same share
    red4 = tr.Reduced(window_ns=6e9, n_devices=4, busy_ns=5e9,
                      exposed_collective_ns=0, top_ops=[], idle_by_label=[])
    out4 = dict(out, tokens=4 * out["tokens"])
    assert cellspec.load_plugin("metrics", "step_mfu").read(
        {"reduced": red4, "out": out4, "cell": cell, "peaks": peaks}) == \
        pytest.approx(value)
